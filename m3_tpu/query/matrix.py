"""A vector expression's value, and the label arithmetic the host
evaluator (query/engine.py) and the fused planner (query/plan.py) must
do alike: a leaf of the package that both import."""

from __future__ import annotations

import dataclasses
import re

import numpy as np

from m3_tpu.query import promql

DEFAULT_SUBQUERY_STEP = 60 * 1_000_000_000


@dataclasses.dataclass
class Matrix:
    """Evaluation result: per-series labels + [L, S] step values."""

    labels: list[dict[bytes, bytes]]
    values: np.ndarray  # [L, S] float64, NaN = no sample

    def drop_name(self) -> "Matrix":
        return Matrix(
            [{k: v for k, v in ls.items() if k != b"__name__"} for ls in self.labels],
            self.values,
        )


def expand_go(m: re.Match, repl: str) -> str:
    """Go regexp.Expand semantics for label_replace replacements:
    ``$1`` / ``$name`` (longest word run) / ``${name}``; ``$$`` is a
    literal '$'; an unknown reference expands to the empty string.
    Implemented directly — routing through re.Match.expand would
    re-interpret backslashes in the literal text."""
    out = []
    i = 0
    while i < len(repl):
        c = repl[i]
        if c != "$":
            out.append(c)
            i += 1
            continue
        if i + 1 >= len(repl):
            out.append("$")
            break
        nxt = repl[i + 1]
        if nxt == "$":
            out.append("$")
            i += 2
            continue
        if nxt == "{":
            end = repl.find("}", i + 2)
            if end == -1:
                out.append(repl[i:])
                break
            name = repl[i + 2:end]
            i = end + 1
        else:
            j = i + 1
            while j < len(repl) and (repl[j].isalnum() or repl[j] == "_"):
                j += 1
            name = repl[i + 1:j]
            i = j
            if not name:
                out.append("$")
                continue
        try:
            group = m.group(int(name) if name.isdigit() else name)
        except IndexError:  # unknown reference -> empty string
            group = None
        out.append(group or "")
    return "".join(out)


def signature(labels: dict, match: promql.VectorMatch | None) -> tuple:
    """Label signature for vector matching (on/ignoring semantics)."""
    if match is not None and match.on:
        keep = {l.encode() for l in match.labels}
        return tuple(sorted((k, v) for k, v in labels.items() if k in keep))
    drop = {b"__name__"}
    if match is not None:
        drop |= {l.encode() for l in match.labels}
    return tuple(sorted((k, v) for k, v in labels.items() if k not in drop))
