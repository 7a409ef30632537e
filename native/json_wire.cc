// query_range's matrix reply, rendered to its JSON bytes — the reply
// path's hot loop, in C++ (the role the reference's jsoniter stream
// plays for src/query/api/v1/handler/prometheus/native/common.go
// renderResultsJSON).  ctypes drops the interpreter lock for the call,
// so one client's render runs beside the other clients' engine calls.
//
// The bytes are json.dumps' of query/http.py _matrix_json's document,
// separators ", " and ": " included:
//
//   <head>{"resultType": "matrix", "result": [
//     {"metric": <metric r>, "values": [[<t>, "<v>"], ...]}, ...]}<tail>
//
//   <t> repr(step_times[s] / 1e9), <v> repr(values[r, s]); a NaN point
//   is left out, and so is a row of nothing but NaN.  The metric
//   objects come rendered (json.dumps of each label dict: its escaping
//   stays Python's), as do the reply's head and tail.
//
// Returns the reply's length, or -1 where `cap` may not hold it (the
// caller sizes the buffer from the same worst case: belt and braces).

#include <charconv>
#include <cmath>
#include <cstdint>
#include <cstring>
#include <vector>

namespace {

// the longest repr of a double: -d.dddddddddddddddde-XXX
constexpr int kReprMax = 24;

inline char* put(char* p, const char* s, size_t n) {
  memcpy(p, s, n);
  return p + n;
}

template <size_t N>
inline char* lit(char* p, const char (&s)[N]) {
  return put(p, s, N - 1);
}

// Python's repr(float) of x (never NaN) at p; returns its end.  The
// digits are the shortest that read back as x, which is what to_chars
// gives; the layout is float_repr_style 'short': fixed while
// -4 <= exponent < 16, with ".0" after a whole number, else
// d[.ddd]e+XX with two exponent digits at least, as to_chars writes it.
char* py_repr(double x, char* p) {
  if (std::isinf(x)) return x < 0 ? lit(p, "-inf") : lit(p, "inf");
  char sci[32];
  const char* s = sci;
  const char* end =
      std::to_chars(sci, sci + sizeof sci, x, std::chars_format::scientific)
          .ptr;
  if (*s == '-') *p++ = *s++;
  const char* e = s + 1;
  while (*e != 'e') ++e;
  int exp = 0;
  for (const char* q = e + 2; q < end; ++q) exp = 10 * exp + (*q - '0');
  if (e[1] == '-') exp = -exp;
  if (exp < -4 || exp >= 16) return put(p, s, end - s);
  char digits[20];
  int n = 0;
  digits[n++] = *s;
  for (const char* q = s + 2; q < e; ++q) digits[n++] = *q;
  if (exp < 0) {
    p = lit(p, "0.");
    for (int i = 1; i < -exp; ++i) *p++ = '0';
    return put(p, digits, n);
  }
  if (n <= exp + 1) {
    p = put(p, digits, n);
    for (int i = n; i < exp + 1; ++i) *p++ = '0';
    return lit(p, ".0");
  }
  p = put(p, digits, exp + 1);
  *p++ = '.';
  return put(p, digits + exp + 1, n - exp - 1);
}

}  // namespace

extern "C" {

int64_t matrix_json_render(const char* head, int64_t head_len,
                           const int64_t* step_times, int64_t n_steps,
                           const double* values, int64_t n_rows,
                           const char* metrics, const int64_t* metric_off,
                           const char* tail, int64_t tail_len, char* out,
                           int64_t cap) {
  // a step's seconds are the same in every row: rendered once
  std::vector<char> step_text((size_t)n_steps * kReprMax);
  std::vector<uint8_t> step_len(n_steps);
  for (int64_t s = 0; s < n_steps; ++s) {
    char* t = step_text.data() + s * kReprMax;
    step_len[s] = (uint8_t)(py_repr((double)step_times[s] / 1e9, t) - t);
  }
  // `[<t>, "<v>"], ` a point, `, {"metric": <m>, "values": []}` a row
  const int64_t point_max = 2 * kReprMax + 8;
  const char* const end = out + cap;
  if (head_len + tail_len + 64 > cap) return -1;
  char* p = put(out, head, head_len);
  p = lit(p, "{\"resultType\": \"matrix\", \"result\": [");
  bool first_row = true;
  for (int64_t r = 0; r < n_rows; ++r) {
    const int64_t m_len = metric_off[r + 1] - metric_off[r];
    if (end - p < 32 + m_len + n_steps * point_max + 2 + tail_len) return -1;
    char* const row = p;
    if (!first_row) p = lit(p, ", ");
    p = lit(p, "{\"metric\": ");
    p = put(p, metrics + metric_off[r], m_len);
    p = lit(p, ", \"values\": [");
    const double* v = values + r * n_steps;
    bool any = false;
    for (int64_t s = 0; s < n_steps; ++s) {
      if (std::isnan(v[s])) continue;
      if (any) p = lit(p, ", ");
      *p++ = '[';
      p = put(p, step_text.data() + s * kReprMax, step_len[s]);
      p = lit(p, ", \"");
      p = py_repr(v[s], p);
      p = lit(p, "\"]");
      any = true;
    }
    if (!any) {
      p = row;
      continue;
    }
    p = lit(p, "]}");
    first_row = false;
  }
  p = lit(p, "]}");
  p = put(p, tail, tail_len);
  return p - out;
}

}  // extern "C"
