"""Least time one training step could take on a chip, from shapes and the
chip's published peaks.

The arithmetic is kept here, with the benchmark, so that no later PR can
move the yardstick. The embedding path is bound by HBM bandwidth: every
looked-up row crosses HBM a fixed number of times per step, which depends
on the optimizer:

* sgd, 3 transfers: the forward reads the row, the update reads it and
  writes it;
* adagrad, 7 transfers: the forward reads the row, the backward's
  scatter-add reads and writes it, and the update reads and writes both the
  row and its accumulator;
* adam, 9 transfers: the forward reads the row, the backward's scatter-add
  reads and writes it, and the lazy row-wise update reads and writes the
  row and both of its moments (the same count with one more state array).

That is the optimistic bound: no ids, no gradients, no duplicates removed.
The dense model is bound by the matrix unit's published bf16 peak.
"""

import json
import os

ROW_TRANSFERS = {"sgd": 3, "adagrad": 7, "adam": 9}
_PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def chip_peaks(device_kind: str) -> dict:
    """Published peaks of one chip. A device not in the table is an error,
    not a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no published peaks on file for device_kind {device_kind!r}: "
            f"add it, with its source, to benchmark/peaks.json "
            f"(have {sorted(table)})")
    return table[device_kind]


def embedding_bytes_per_sample(widths, hotness, optimizer_kind,
                               dtype_bytes=4) -> int:
    """HBM bytes one sample's lookups must move in one training step:
    per input, hotness rows of the table's width, each transferred
    ROW_TRANSFERS[optimizer] times."""
    n = ROW_TRANSFERS[optimizer_kind]
    return sum(n * w * h * dtype_bytes for w, h in zip(widths, hotness))


def least_step_s(peaks, samples_per_chip, emb_bytes_per_sample,
                 flops_per_sample):
    """(seconds, which bound): the larger of HBM bytes over peak bandwidth
    and matmul flops over peak bf16, for one chip's share of a step."""
    hbm = samples_per_chip * emb_bytes_per_sample / (peaks["hbm_gb_per_s"] * 1e9)
    mxu = samples_per_chip * flops_per_sample / (peaks["bf16_tflop_per_s"] * 1e12)
    return (hbm, "hbm") if hbm >= mxu else (mxu, "mxu")
