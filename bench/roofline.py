"""Peaks of the chips the benchmark runs on, and the least bytes a batch of
ODYS queries has to move.

The byte count depends on the work only (each query's driver list lengths,
its condition, its answer and the window), never on how a kernel reads,
so a kernel that reads a tile twice shows as a lower share of its roofline,
and no share can pass 100%.  What it counts, per real query (inert padding
slots do no work a user asked for):

- the intersection: the driver window's postings, read once (``n_eff``
  int32s, where ``n_eff`` is the driver's window length: its first
  ``window`` postings, or under merge-on-read the base window merged with
  the delta list, cut to ``window``), and its attrs (one int32 each) when
  the query is site-limited; a single keyword without a site needs only
  its first ``k`` postings, since its ``n_hits`` follows from the list
  length (not so under merge-on-read, where dead entries in the window
  have to be seen).  Other keywords' lists are probed; a probe that skips by the
  block maxima need not read a posting, so none is counted.  The answer,
  written once: its docIDs and ``n_hits``.
- the delta merge: the driver's base window and its delta list, read once
  (postings, and attrs when site-limited).
"""
from __future__ import annotations

#: Per ``jax.Device.device_kind``: HBM bytes per second, and the source.
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 819 GBps HBM2 per chip",
    },
}

WORD = 4  # bytes of one int32 docID or attr


def hbm_bytes_per_s(device_kind: str) -> float:
    """The chip's peak HBM bandwidth; a chip not in :data:`PEAKS` is an error."""
    try:
        return PEAKS[device_kind]["hbm_bytes_per_s"]
    except KeyError:
        raise KeyError(f"no peak recorded for device kind {device_kind!r}") from None


def driver_window(base_len: int, delta_len: int, window: int) -> int:
    """Postings in the driver's window (base window merged with the delta)."""
    return min(min(base_len, window) + delta_len, window)


def intersect_bytes(*, base_len: int, delta_len: int, n_terms: int,
                    limited: bool, merged: bool, k: int, n_answer: int,
                    window: int) -> int:
    n_eff = driver_window(base_len, delta_len, window)
    if n_terms == 1 and not limited and not merged:
        read = min(k, n_eff) * WORD
    else:
        read = n_eff * WORD * (2 if limited else 1)
    return read + (n_answer + 1) * WORD


def delta_merge_bytes(*, base_len: int, delta_len: int, limited: bool,
                      window: int) -> int:
    return (min(base_len, window) + delta_len) * WORD * (2 if limited else 1)


def share_pct(n_bytes: float, seconds: float, device_kind: str) -> float | None:
    """Least time the bytes need at peak over the measured time, in %;
    None where nothing was measured."""
    if seconds <= 0 or n_bytes <= 0:
        return None
    return 100.0 * n_bytes / hbm_bytes_per_s(device_kind) / seconds
