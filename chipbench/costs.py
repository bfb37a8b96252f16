"""Work the benchmark counts for its roofline and utilization shares,
computed from shapes, and the peaks it divides by.

- ``assess_bytes``: algorithmic bytes of one call of an assessment
  method over the attempt rows and the jobs that a tick really holds,
  not the padding of the program's arrays: each reduction reads its
  rows x columns once and writes its buckets x accumulators once (4
  bytes each; boolean outputs 1 byte). It counts the same work whichever
  backend, padding or fusion computes it.
- ``train_flops_per_token``: forward and backward FLOPs of a dense
  decoder per token, from its published widths: ``6 x`` the parameters
  that multiply (every layer's matrices and biases, and the output
  head, tied or not; the embedding lookup multiplies nothing), plus
  ``12 x layers x width x sequence`` for the attention scores and their
  weighted sum. Recomputation under rematerialization is not counted.
- ``peaks``: the table in ``peaks.json``, keyed by ``device_kind``. A
  device that is not in the table is an error, not a default.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
WORD = 4

# Per-row columns each reduction reads (order and the gathers included).
_ROW_COLS = {
    "spatial_hits": 14,     # order a_state t_state kind node start work_done
                            # work_total last_sync fetched deps compute
                            # active job
    "temporal_zeta": 16,    # spatial's + sample mark + ζ at the mark
    "winning": 15,          # spatial's + spec
    "reap_rows": 5,         # order active t_state a_state task segment
    "late_victims": 15,     # spatial's + spec
}


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def assess_bytes(method: str, rows: int, jobs: int, n_nodes: int) -> int:
    """Algorithmic bytes of one call of ``method`` over ``rows`` attempt
    rows, ``jobs`` active jobs and ``n_nodes`` nodes."""
    if method == "failure_masks":
        # node_hb, marked, declared, thresholds in; two masks out
        return n_nodes * 4 * WORD + 2 * n_nodes
    reads = rows * _ROW_COLS[method] * WORD
    if method == "spatial_hits":
        buckets = jobs * 2 * n_nodes
        return reads + buckets * 2 * WORD + jobs * n_nodes
    if method == "temporal_zeta":
        buckets = jobs * n_nodes
        return reads + buckets * 3 * WORD + rows * 2 * WORD
    if method == "winning":
        return reads + rows * 5 * WORD + jobs
    if method == "reap_rows":
        return reads + rows * WORD + rows
    if method == "late_victims":
        return reads + rows * 3 * WORD + jobs * WORD
    raise KeyError(method)


def window_assess_bytes(tick_work: dict, n_nodes: int) -> int:
    """Bytes of a window's assessment work. ``tick_work`` lists, per
    method, the ``(rows, jobs)`` of each tick that called it: a method
    asked several times in a tick (``winning``, once per job) is answered
    by one device call."""
    return sum(assess_bytes(m, rows, jobs, n_nodes)
               for m, work in tick_work.items()
               if m in _ROW_COLS or m == "failure_masks"
               for rows, jobs in work)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    d = cfg["hidden_size"]
    L = cfg["num_hidden_layers"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = d // h
    ff = cfg["intermediate_size"]
    v = cfg["vocab_size"]
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    bias = h * hd + 2 * kv * hd if cfg.get("qkv_bias", False) else 0
    mlp = 3 * d * ff
    matmul_params = L * (attn + bias + mlp) + v * d
    return 6.0 * matmul_params + 12.0 * L * d * seq_len
