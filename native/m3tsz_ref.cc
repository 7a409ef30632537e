// Scalar M3TSZ decoder + windowed-mean downsample, C++.
//
// Two roles:
//  1. CPU baseline: the reference implementation is pure Go
//     (SURVEY.md §2.4) and no Go toolchain exists in this image, so this
//     native scalar decoder stands in as the single-core CPU baseline the
//     TPU path is measured against (same algorithmic shape as
//     ref: src/dbnode/encoding/m3tsz/iterator.go — branchy per-bit
//     decode, per-series loop).
//  2. Seed of the native runtime layer: the framework's host-side
//     services link against this library for wire-compat decode without
//     paying Python costs.
//
// Grammar: docs/m3tsz_format.md (int-optimized + float modes, markers).
// Annotations/time-unit changes are not handled here (the Python oracle
// covers those paths); streams containing them abort that series cleanly.
//
// Build: g++ -O2 -shared -fPIC -o libm3tsz_ref.so m3tsz_ref.cc

#include <cstdint>
#include <cstring>
#include <cmath>
#include <functional>
#include <thread>
#include <vector>

namespace {

struct BitReader {
  const uint8_t* data;
  int64_t nbits;
  int64_t pos = 0;
  bool oob = false;  // set on any read past the end; reads yield 0

  bool ok(int64_t n) const { return pos + n <= nbits; }

  uint64_t read(int n) {
    if (pos + n > nbits) {
      oob = true;
      pos = nbits;
      return 0;
    }
    uint64_t out = 0;
    int64_t p = pos;
    pos += n;
    while (n > 0) {
      int off = p & 7;
      int take = 8 - off < n ? 8 - off : n;
      uint8_t byte = data[p >> 3];
      out = (out << take) | ((byte >> (8 - off - take)) & ((1u << take) - 1));
      p += take;
      n -= take;
    }
    return out;
  }

  uint64_t peek(int n) {
    int64_t save = pos;
    uint64_t v = read(n);
    pos = save;
    return v;
  }
};

inline int64_t sign_extend(uint64_t v, int bits) {
  int shift = 64 - bits;
  return ((int64_t)(v << shift)) >> shift;
}

constexpr uint64_t kMarkerOpcode = 0x100;  // 9 bits
constexpr int kMarkerBits = 11;            // opcode + 2-bit value

// Decode one series; returns number of datapoints, -1 on unsupported
// construct, or -2 when check_complete is set and the stream still has
// datapoints beyond max_dp (the cap silently truncating would otherwise
// be undetectable to callers that trust externally-supplied counts).
// Writes up to max_dp (time_ns, value) pairs.
int decode_series(const uint8_t* data, int64_t nbytes, int64_t unit_nanos,
                  int64_t* out_t, double* out_v, int max_dp,
                  bool check_complete = false) {
  BitReader r{data, nbytes * 8};
  if (!r.ok(64 + kMarkerBits)) return 0;

  int64_t prev_time = (int64_t)r.read(64);
  int64_t prev_delta = 0;
  uint64_t prev_float = 0, prev_xor = 0;
  int64_t int_val = 0;
  int sig = 0, mult = 0;
  bool is_float = false;
  static const double kDiv[7] = {1, 10, 100, 1000, 10000, 100000, 1000000};

  int n = 0;
  while (n < max_dp) {
    // --- timestamp: marker lookahead then delta-of-delta ---
    if (r.ok(kMarkerBits)) {
      uint64_t m = r.peek(kMarkerBits);
      if ((m >> 2) == kMarkerOpcode) {
        if ((m & 3) == 0) return n;  // end of stream
        return -1;                   // annotation/time-unit: unsupported
      }
    }
    if (!r.ok(1)) return n;
    int64_t dod;
    if (r.read(1) == 0) {
      dod = 0;
    } else if (r.read(1) == 0) {
      dod = sign_extend(r.read(7), 7);
    } else if (r.read(1) == 0) {
      dod = sign_extend(r.read(9), 9);
    } else if (r.read(1) == 0) {
      dod = sign_extend(r.read(12), 12);
    } else {
      dod = sign_extend(r.read(32), 32);
    }
    prev_delta += dod * unit_nanos;
    prev_time += prev_delta;

    // --- value (int-optimized grammar) ---
    auto read_sig_mult = [&]() {
      if (r.read(1) == 1) {
        sig = r.read(1) == 0 ? 0 : (int)r.read(6) + 1;
      }
      if (r.read(1) == 1) mult = (int)r.read(3);
    };
    auto read_int_diff = [&]() {
      double s = r.read(1) == 1 ? 1.0 : -1.0;
      int_val += (int64_t)s * (int64_t)r.read(sig);
    };
    auto read_xor = [&]() {
      if (r.read(1) == 0) {
        prev_xor = 0;
        return;
      }
      if (r.read(1) == 0) {
        int lead = __builtin_clzll(prev_xor | 1);
        int trail = prev_xor ? __builtin_ctzll(prev_xor) : 0;
        if (prev_xor == 0) lead = 64, trail = 0;
        int meaningful = 64 - lead - trail;
        prev_xor = meaningful > 0 ? r.read(meaningful) << trail : 0;
      } else {
        int lead = (int)r.read(6);
        int meaningful = (int)r.read(6) + 1;
        int trail = 64 - lead - meaningful;
        if (trail < 0) {  // corrupt record; stop this series cleanly
          r.oob = true;
          return;
        }
        prev_xor = r.read(meaningful) << trail;
      }
      prev_float ^= prev_xor;
    };

    if (n == 0) {
      if (r.read(1) == 1) {  // float mode
        prev_float = r.read(64);
        prev_xor = prev_float;
        is_float = true;
      } else {
        read_sig_mult();
        read_int_diff();
      }
    } else {
      if (r.read(1) == 0) {   // update branch
        if (r.read(1) == 1) { // repeat
        } else if (r.read(1) == 1) {
          prev_float = r.read(64);
          prev_xor = prev_float;
          is_float = true;
        } else {
          read_sig_mult();
          read_int_diff();
          is_float = false;
        }
      } else if (is_float) {
        read_xor();
      } else {
        read_int_diff();
      }
    }

    if (mult > 6) return -1;  // 3-bit field allows 7; invalid like the oracle
    if (r.oob) return n;      // truncated/corrupt: keep the clean prefix

    if (out_t != nullptr) {  // null outputs = count-only pass
      out_t[n] = prev_time;
      if (is_float) {
        double d;
        std::memcpy(&d, &prev_float, 8);
        out_v[n] = d;
      } else {
        out_v[n] = (double)int_val / kDiv[mult];
      }
    }
    n++;
  }
  if (check_complete && n == max_dp) {
    // the stream must now be at its end-of-stream marker (or out of
    // readable bits — zero padding): anything else means max_dp
    // silently capped a longer stream
    if (r.ok(kMarkerBits)) {
      uint64_t m = r.peek(kMarkerBits);
      if ((m >> 2) != kMarkerOpcode || (m & 3) != 0) return -2;
    } else if (r.ok(1)) {
      // fewer than kMarkerBits left: only zero padding is legal
      int64_t rest = r.nbits - r.pos;
      if (r.read((int)rest) != 0) return -2;
    }
  }
  return n;
}


// Split [0, n) into contiguous chunks over a small thread pool (the
// shared scaffold for every threaded batch entry point in this TU).
void run_rows_threaded(int64_t n, int n_threads,
                       const std::function<void(int64_t, int64_t)>& work) {
  if (n_threads <= 0) {
    unsigned hw = std::thread::hardware_concurrency();
    n_threads = hw ? static_cast<int>(hw) : 1;
  }
  if (n_threads > n) n_threads = n ? static_cast<int>(n) : 1;
  if (n_threads == 1) {
    work(0, n);
    return;
  }
  std::vector<std::thread> pool;
  int64_t chunk = (n + n_threads - 1) / n_threads;
  for (int t = 0; t < n_threads; t++) {
    int64_t lo = t * chunk;
    int64_t hi = lo + chunk < n ? lo + chunk : n;
    if (lo >= hi) break;
    pool.emplace_back(work, lo, hi);
  }
  for (auto& th : pool) th.join();
}

}  // namespace

extern "C" {

// Decode L streams (offsets[i]..offsets[i+1] into blob) and reduce each to
// windowed means over `window` consecutive datapoints.  Returns total
// datapoints decoded.  out_means is [L * n_windows].
int64_t m3tsz_decode_downsample(const uint8_t* blob, const int64_t* offsets,
                                int64_t n_series, int64_t unit_nanos,
                                int max_dp, int window, double* out_means) {
  int n_windows = max_dp / window;
  int64_t* t = new int64_t[max_dp];
  double* v = new double[max_dp];
  int64_t total = 0;
  for (int64_t i = 0; i < n_series; i++) {
    const uint8_t* p = blob + offsets[i];
    int64_t len = offsets[i + 1] - offsets[i];
    int n = decode_series(p, len, unit_nanos, t, v, max_dp);
    if (n < 0) n = 0;
    total += n;
    for (int w = 0; w < n_windows; w++) {
      double sum = 0;
      int cnt = 0;
      for (int j = w * window; j < (w + 1) * window && j < n; j++) {
        // NaN datapoints count toward the divisor but not the sum —
        // gauge semantics parity with the TPU path (ref: gauge.go:62-66)
        cnt++;
        if (!std::isnan(v[j])) sum += v[j];
      }
      out_means[i * n_windows + w] = cnt ? sum / cnt : 0.0;
    }
  }
  delete[] t;
  delete[] v;
  return total;
}

// Decode-only entry (correctness cross-check from Python tests).
int m3tsz_decode_one(const uint8_t* data, int64_t nbytes, int64_t unit_nanos,
                     int64_t* out_t, double* out_v, int max_dp) {
  return decode_series(data, nbytes, unit_nanos, out_t, out_v, max_dp);
}

// Threaded count-only pass: datapoints per stream without storing them
// (-1 marks unsupported constructs).  A stream's dp count is not
// recoverable from its byte length (4.5-26 bits/dp depending on data),
// so batch readers count first and size the decode grid exactly.
void m3tsz_count_batch(const uint8_t* blob, const int64_t* offsets,
                       int64_t n_series, int64_t unit_nanos, int n_threads,
                       int64_t* out_n) {
  run_rows_threaded(n_series, n_threads, [&](int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; i++) {
      const uint8_t* p = blob + offsets[i];
      int64_t len = offsets[i + 1] - offsets[i];
      out_n[i] =
          decode_series(p, len, unit_nanos, nullptr, nullptr, 1 << 30);
    }
  });
}

// Fused decode+merge: decode each of M block streams DIRECTLY into its
// final position inside the packed [n_lanes, n_cap] batch — no
// intermediate per-stream grids, no separate merge pass (on a
// single-core host the read path is memory-bandwidth-bound and this
// halves the traffic).  row_dst[m] = flat destination offset
// (lane * n_cap + running per-lane position), precomputed by the
// caller from a count pass.  Writes per-row dp counts, first/last
// timestamps (for the caller's cross-row order check) and a per-row
// sorted flag (0 = this row's timestamps went backwards; caller falls
// back to the sorting merge).  Tail positions [lane_total, n_cap) are
// padded with INT64_MAX / NaN by the caller or a later pass.
void m3tsz_decode_merged(const uint8_t* blob, const int64_t* offsets,
                         int64_t M, int64_t unit_nanos,
                         const int64_t* row_dst, const int64_t* row_cap,
                         int n_threads, int64_t* out_t, double* out_v,
                         int64_t* row_n, int64_t* row_first,
                         int64_t* row_last, uint8_t* row_sorted) {
  auto work = [&](int64_t lo, int64_t hi) {
    for (int64_t m = lo; m < hi; m++) {
      const uint8_t* p = blob + offsets[m];
      int64_t len = offsets[m + 1] - offsets[m];
      int64_t* t = out_t + row_dst[m];
      double* v = out_v + row_dst[m];
      // check_complete: row_cap may come from stored (v2-fileset)
      // counts — a stale/low count must surface as -2, not silently
      // truncate the stream's tail
      int n = decode_series(p, len, unit_nanos, t, v,
                            static_cast<int>(row_cap[m]), true);
      row_n[m] = n;
      if (n > 0) {
        row_first[m] = t[0];
        row_last[m] = t[n - 1];
        uint8_t sorted = 1;
        for (int i = 1; i < n; i++)
          if (t[i] < t[i - 1]) {
            sorted = 0;
            break;
          }
        row_sorted[m] = sorted;
      } else {
        row_first[m] = INT64_MAX;
        row_last[m] = INT64_MIN;
        row_sorted[m] = 1;
      }
    }
  };
  run_rows_threaded(M, n_threads, work);
}

// Pad each lane's tail [lane_counts[l], n_cap) with +inf / NaN.
void pad_lane_tails(int64_t* out_t, double* out_v,
                    const int64_t* lane_counts, int64_t n_lanes,
                    int64_t n_cap) {
  const double nan = std::nan("");
  for (int64_t l = 0; l < n_lanes; l++) {
    for (int64_t i = lane_counts[l]; i < n_cap; i++) {
      out_t[l * n_cap + i] = INT64_MAX;
      out_v[l * n_cap + i] = nan;
    }
  }
}

// Threaded raw batch decode: L streams into [L, max_dp] timestamp/value
// grids with per-stream counts (-1 marks an unsupported construct; the
// Python caller patches those lanes with its scalar oracle).  This is
// the CPU serving path for fan-out reads — each stream is an
// independent state machine, so lanes split into contiguous chunks
// over a small thread pool (same pattern as m3tsz_prepare.cc).
void m3tsz_decode_batch(const uint8_t* blob, const int64_t* offsets,
                        int64_t n_series, int64_t unit_nanos, int max_dp,
                        int n_threads, int64_t* out_t, double* out_v,
                        int64_t* out_n) {
  run_rows_threaded(n_series, n_threads, [&](int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; i++) {
      const uint8_t* p = blob + offsets[i];
      int64_t len = offsets[i + 1] - offsets[i];
      out_n[i] = decode_series(p, len, unit_nanos, out_t + i * max_dp,
                               out_v + i * max_dp, max_dp);
    }
  });
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Scalar M3TSZ encoder — wire-identical to the framework's Python scalar
// encoder (m3_tpu/ops/m3tsz_scalar.py, itself parity-tested against the
// reference grammar: ref src/dbnode/encoding/m3tsz/encoder.go).  Serves as
// the single-core CPU baseline for the batched TPU encode bench and as a
// second roundtrip oracle.  Second-aligned timestamps, no annotations or
// mid-stream time-unit changes (the bench/storage hot path).

namespace enc {

constexpr int kSigField = 6;
constexpr int kMultBits = 3;
constexpr int kSigDiffThreshold = 3;   // ref: m3tsz.go:57
constexpr int kSigRepeatThreshold = 5; // ref: m3tsz.go:58
constexpr int kMaxMult = 6;
constexpr double kMaxOptInt = 1e13;    // ref: m3tsz.go:67
constexpr double kMaxInt64 = 9223372036854775808.0;

struct BitWriter {
  uint8_t* buf;
  int64_t bitpos = 0;

  void write_bits(uint64_t v, int n) {
    // MSB-first append
    for (int i = n - 1; i >= 0; i--) {
      uint64_t bit = (v >> i) & 1;
      if ((bitpos & 7) == 0) buf[bitpos >> 3] = 0;
      buf[bitpos >> 3] |= uint8_t(bit << (7 - (bitpos & 7)));
      bitpos++;
    }
  }
  void write_bit(int b) { write_bits(uint64_t(b), 1); }
};

inline int num_sig_bits(uint64_t mag) {
  return mag == 0 ? 0 : 64 - __builtin_clzll(mag);
}

struct SigTracker {  // ref: int_sig_bits_tracker.go:68-91
  int num_sig = 0;
  int cur_highest_lower = 0;
  int num_lower = 0;

  int track(int sig) {
    int new_sig = num_sig;
    if (sig > num_sig) {
      new_sig = sig;
    } else if (num_sig - sig >= kSigDiffThreshold) {
      if (num_lower == 0 || sig > cur_highest_lower) cur_highest_lower = sig;
      num_lower++;
      if (num_lower >= kSigRepeatThreshold) {
        new_sig = cur_highest_lower;
        num_lower = 0;
      }
    } else {
      num_lower = 0;
    }
    return new_sig;
  }
};

// ref: m3tsz.go:78-118 convertToIntFloat
inline void convert_to_int_float(double v, int cur_max_mult, double* out_val,
                                 int* out_mult, bool* out_is_float) {
  if (cur_max_mult == 0 && v < kMaxInt64 && !std::isinf(v)) {
    double intpart;
    double frac = std::modf(v, &intpart);
    if (frac == 0) {
      *out_val = intpart;
      *out_mult = 0;
      *out_is_float = false;
      return;
    }
  }
  double val = v * std::pow(10.0, cur_max_mult);
  double sign = 1.0;
  if (v < 0) {
    sign = -1.0;
    val = -val;
  }
  int mult = cur_max_mult;
  while (mult <= kMaxMult && val < kMaxOptInt) {
    double intpart;
    double frac = std::modf(val, &intpart);
    if (frac == 0) {
      *out_val = sign * intpart;
      *out_mult = mult;
      *out_is_float = false;
      return;
    }
    if (frac < 0.1) {
      if (std::nextafter(val, 0.0) <= intpart) {
        *out_val = sign * intpart;
        *out_mult = mult;
        *out_is_float = false;
        return;
      }
    } else if (frac > 0.9) {
      double nxt = intpart + 1;
      if (std::nextafter(val, nxt) >= nxt) {
        *out_val = sign * nxt;
        *out_mult = mult;
        *out_is_float = false;
        return;
      }
    }
    val *= 10.0;
    mult++;
  }
  *out_val = v;
  *out_mult = 0;
  *out_is_float = true;
}

inline uint64_t float_bits(double v) {
  uint64_t b;
  std::memcpy(&b, &v, 8);
  return b;
}

struct Encoder {
  BitWriter w;
  // timestamp state
  int64_t prev_time;
  int64_t prev_delta = 0;
  int64_t unit_nanos;
  int default_value_bits;
  // value state
  int64_t num_encoded = 0;
  uint64_t prev_float_bits = 0;
  uint64_t prev_xor = 0;
  double int_val = 0.0;
  int max_mult = 0;
  bool is_float = false;
  SigTracker sig;

  Encoder(uint8_t* buf, int64_t start_nanos) : prev_time(start_nanos) {
    w.buf = buf;
    if (start_nanos % 1000000000LL == 0) {
      unit_nanos = 1000000000LL;   // SECOND scheme: 32-bit default bucket
      default_value_bits = 32;
    } else {
      unit_nanos = 1;              // NANOSECOND scheme: 64-bit default
      default_value_bits = 64;
    }
  }

  void write_time(int64_t t) {  // ref: timestamp_encoder.go WriteTime
    if (num_encoded == 0) w.write_bits(uint64_t(prev_time), 64);
    int64_t delta = t - prev_time;
    prev_time = t;
    int64_t raw_dod = delta - prev_delta;
    // truncate toward zero, matching Go integer division
    int64_t dod = raw_dod < 0 ? -((-raw_dod) / unit_nanos)
                              : raw_dod / unit_nanos;
    prev_delta = delta;
    if (dod == 0) {
      w.write_bit(0);
      return;
    }
    // buckets: (0b10,2,7) (0b110,3,9) (0b1110,4,12), ref scheme.go:42-52
    static const int opcodes[3] = {0b10, 0b110, 0b1110};
    static const int opbits[3] = {2, 3, 4};
    static const int valbits[3] = {7, 9, 12};
    for (int i = 0; i < 3; i++) {
      int64_t lo = -(1LL << (valbits[i] - 1));
      int64_t hi = (1LL << (valbits[i] - 1)) - 1;
      if (lo <= dod && dod <= hi) {
        w.write_bits(uint64_t(opcodes[i]), opbits[i]);
        w.write_bits(uint64_t(dod) & ((1ULL << valbits[i]) - 1), valbits[i]);
        return;
      }
    }
    w.write_bits(0b1111, 4);
    w.write_bits(uint64_t(dod) & ((default_value_bits == 64)
                                      ? ~0ULL
                                      : ((1ULL << 32) - 1)),
                 default_value_bits);
  }

  void write_full_float(uint64_t bits) {
    w.write_bits(bits, 64);
    prev_float_bits = bits;
    prev_xor = bits;
  }

  void write_float_xor(uint64_t bits) {
    uint64_t x = prev_float_bits ^ bits;
    if (x == 0) {
      w.write_bit(0);
    } else {
      int prev_lead = prev_xor ? __builtin_clzll(prev_xor) : 64;
      int prev_trail = prev_xor ? __builtin_ctzll(prev_xor) : 0;
      int lead = __builtin_clzll(x);
      int trail = __builtin_ctzll(x);
      if (lead >= prev_lead && trail >= prev_trail) {
        w.write_bits(0b10, 2);
        w.write_bits(x >> prev_trail, 64 - prev_lead - prev_trail);
      } else {
        int meaningful = 64 - lead - trail;
        w.write_bits(0b11, 2);
        w.write_bits(uint64_t(lead), 6);
        w.write_bits(uint64_t(meaningful - 1), 6);
        w.write_bits(x >> trail, meaningful);
      }
    }
    prev_xor = x;
    prev_float_bits = bits;
  }

  void write_int_sig_mult(int s, int mult, bool float_changed) {
    if (sig.num_sig != s) {
      w.write_bit(1);  // opcodeUpdateSig
      if (s == 0) {
        w.write_bit(0);
      } else {
        w.write_bit(1);
        w.write_bits(uint64_t(s - 1), kSigField);
      }
    } else {
      w.write_bit(0);
    }
    sig.num_sig = s;
    if (mult > max_mult) {
      w.write_bit(1);  // opcodeUpdateMult
      w.write_bits(uint64_t(mult), kMultBits);
      max_mult = mult;
    } else if (sig.num_sig == s && max_mult == mult && float_changed) {
      w.write_bit(1);
      w.write_bits(uint64_t(max_mult), kMultBits);
    } else {
      w.write_bit(0);
    }
  }

  void write_int_diff(uint64_t mag, bool add) {
    w.write_bit(add ? 1 : 0);  // opcodeNegative semantics, ref decoder
    w.write_bits(mag, sig.num_sig);
  }

  void write_first_value(double v) {
    double val;
    int mult;
    bool isf;
    convert_to_int_float(v, 0, &val, &mult, &isf);
    if (isf) {
      w.write_bit(1);  // float mode
      write_full_float(float_bits(v));
      is_float = true;
      max_mult = mult;
      return;
    }
    w.write_bit(0);  // int mode
    int_val = val;
    bool add = val >= 0;
    double mag_f = std::fabs(val);
    uint64_t mag = mag_f >= kMaxInt64 ? (1ULL << 63) : uint64_t(mag_f);
    write_int_sig_mult(num_sig_bits(mag), mult, false);
    write_int_diff(mag, add);
  }

  void write_float_transition(uint64_t bits, int mult) {
    if (!is_float) {
      w.write_bit(0);  // update
      w.write_bit(0);  // no repeat
      w.write_bit(1);  // float mode
      write_full_float(bits);
      is_float = true;
      max_mult = mult;
      return;
    }
    if (bits == prev_float_bits) {
      w.write_bit(0);  // update
      w.write_bit(1);  // repeat
      return;
    }
    w.write_bit(1);  // no update
    write_float_xor(bits);
  }

  void write_int_val(double val, int mult, bool isf, double diff) {
    if (diff == 0 && isf == is_float && mult == max_mult) {
      w.write_bit(0);  // update
      w.write_bit(1);  // repeat
      return;
    }
    bool add = diff < 0;  // encoder stores prev-new
    double mag_f = std::fabs(diff);
    uint64_t mag = uint64_t(mag_f);
    int new_sig = sig.track(num_sig_bits(mag));
    bool float_changed = isf != is_float;
    if (mult > max_mult || sig.num_sig != new_sig || float_changed) {
      w.write_bit(0);  // update
      w.write_bit(0);  // no repeat
      w.write_bit(0);  // int mode
      write_int_sig_mult(new_sig, mult, float_changed);
      write_int_diff(mag, add);
      is_float = false;
    } else {
      w.write_bit(1);  // no update
      write_int_diff(mag, add);
    }
    int_val = val;
  }

  void write_next_value(double v) {
    double val;
    int mult;
    bool isf;
    convert_to_int_float(v, max_mult, &val, &mult, &isf);
    double diff = isf ? 0.0 : int_val - val;
    if (isf || diff >= kMaxInt64 || diff <= -kMaxInt64) {
      write_float_transition(float_bits(val), mult);
      return;
    }
    write_int_val(val, mult, isf, diff);
  }

  void encode(int64_t t, double v) {
    write_time(t);
    if (num_encoded == 0) {
      write_first_value(v);
    } else {
      write_next_value(v);
    }
    num_encoded++;
  }

  int64_t finalize() {  // EOS marker; returns byte length
    if (num_encoded == 0) return 0;
    w.write_bits(0x100, 9);
    w.write_bits(0, 2);
    return (w.bitpos + 7) / 8;
  }
};

}  // namespace enc

extern "C" {

// Encode L series of T datapoints each (int-optimized M3TSZ, second or
// nanosecond scheme by start alignment).  ts/vs are [L*T] row-major;
// starts is [L]; out is [L*stride] with per-series byte lengths in
// out_bytes.  Returns total bytes written, or -1 if any series needs
// more than `stride` bytes.
// Columnar ragged encode: lane l's datapoints are the slice
// [bounds[l], bounds[l+1]) of ts/vs (lane-sorted columnar form — the
// shard seal path's natural layout; no dense [L, T] scatter needed).
// Threaded across lanes.  Returns total bytes, or -1 if any series
// overflows `stride` bytes.
int64_t m3tsz_encode_columnar(const int64_t* bounds, const int64_t* ts,
                              const double* vs, int64_t L,
                              const int64_t* starts, uint8_t* out,
                              int64_t stride, int n_threads,
                              int64_t* out_bytes) {
  std::vector<int64_t> totals(L, 0);
  std::vector<char> overflow(L, 0);
  run_rows_threaded(L, n_threads, [&](int64_t lo_l, int64_t hi_l) {
    for (int64_t l = lo_l; l < hi_l; l++) {
      int64_t lo = bounds[l], hi = bounds[l + 1];
      if (hi <= lo) {
        out_bytes[l] = 0;
        continue;
      }
      enc::Encoder e(out + l * stride, starts[l]);
      int64_t cap_bits = (stride - 16) * 8;
      for (int64_t i = lo; i < hi; i++) {
        if (e.w.bitpos >= cap_bits) {
          overflow[l] = 1;
          break;
        }
        e.encode(ts[i], vs[i]);
      }
      if (overflow[l]) continue;
      int64_t nb = e.finalize();
      out_bytes[l] = nb;
      totals[l] = nb;
    }
  });
  int64_t total = 0;
  for (int64_t l = 0; l < L; l++) {
    if (overflow[l]) return -1;
    total += totals[l];
  }
  return total;
}

int64_t m3tsz_encode_batch(const int64_t* ts, const double* vs, int64_t L,
                           int64_t T, const int64_t* starts, uint8_t* out,
                           int64_t stride, int64_t* out_bytes) {
  int64_t total = 0;
  for (int64_t l = 0; l < L; l++) {
    enc::Encoder e(out + l * stride, starts[l]);
    // worst-case record ~ (36+80)/8 = 15 bytes; bail before overflow
    int64_t cap_bits = (stride - 16) * 8;
    for (int64_t i = 0; i < T; i++) {
      if (e.w.bitpos >= cap_bits) return -1;
      e.encode(ts[l * T + i], vs[l * T + i]);
    }
    int64_t nb = e.finalize();
    out_bytes[l] = nb;
    total += nb;
  }
  return total;
}

}  // extern "C"
