"""The comparison that decides ``correct``.

Every row of every request sent in the window is compared with the
plain reference (``cell.reference`` over the schedule's requests):

* ``false_negatives``: records of the request's tenant (its relation's
  records and its own) that answered False. The configuration's
  guarantee; limit 0.
* ``mismatched_rows``: rows whose answer differs from the float32
  filter's, leaving out rows within ``filters.BORDER_LOGIT`` of the
  threshold (their answer hangs on rounding). Limit 0.
* ``unanswered_requests``: requests that never resolved or resolved
  with an error. Limit 0.

A closed loop sends its schedule's requests over and over: sent
request ``k`` is schedule entry ``sched_idx[k]``, and its answers are
held against that entry's reference.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.lib.cell import Reference, spans

LIMITS = {"false_negatives": 0, "mismatched_rows": 0,
          "unanswered_requests": 0}


def compare(answers: np.ndarray, resolved: np.ndarray, failed: np.ndarray,
            sched_idx: np.ndarray, rows: np.ndarray, ref: Reference,
            chunk: int = 1024) -> Dict[str, int]:
    """``answers`` holds the sent requests' rows back to back, in the
    order sent; ``rows[e]`` is schedule entry ``e``'s size. Requests not
    resolved count as unanswered and their rows are left out of the row
    counts."""
    rows = np.asarray(rows, np.int64)
    n_sent = rows[sched_idx]
    start = np.concatenate([[0], np.cumsum(n_sent)[:-1]]).astype(np.int64)
    ok = np.flatnonzero(resolved & ~failed)
    out = {"false_negatives": 0, "mismatched_rows": 0,
           "unanswered_requests": int(len(resolved) - len(ok)),
           "border_rows": 0, "border_mismatched": 0,
           "mismatch_margin_max": 0.0, "rows_checked": 0}
    for s in range(0, len(ok), chunk):
        part = ok[s:s + chunk]
        got = answers[spans(start[part], n_sent[part])]
        at = spans(ref.start[sched_idx[part]], n_sent[part])
        border = ref.border[at]
        diff = got != ref.answers[at]
        out["false_negatives"] += int((ref.is_record[at] & ~got).sum())
        out["mismatched_rows"] += int((diff & ~border).sum())
        out["border_rows"] += int(border.sum())
        out["border_mismatched"] += int((diff & border).sum())
        if diff.any():
            out["mismatch_margin_max"] = max(out["mismatch_margin_max"],
                                             float(ref.margin[at][diff].max()))
        out["rows_checked"] += len(at)
    return out


def verdict(numbers: Dict[str, int]) -> bool:
    return all(numbers[k] <= v for k, v in LIMITS.items())


def lines(numbers: Dict[str, int]) -> List[str]:
    """Each compared number beside its limit, then what was left out."""
    out = [f"check {k} {numbers[k]} limit {v}" for k, v in LIMITS.items()]
    out.append(f"check rows_checked {numbers['rows_checked']} border_rows "
               f"{numbers['border_rows']} (left out of mismatched_rows; "
               f"{numbers['border_mismatched']} of them differ) "
               f"mismatch_margin_max {numbers['mismatch_margin_max']:.3e}")
    return out
