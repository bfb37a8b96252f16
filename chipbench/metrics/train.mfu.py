"""Model FLOP utilization of the whole training step: forward and
backward FLOPs per token from the published widths
(``costs.train_flops_per_token``; recomputation not counted) times the
window's tokens per second, over the chip's bf16 peak."""
from chipbench import costs


def read(run):
    tps = run.counters.get("tokens_per_s")
    if not tps:
        return None
    model = run.config["model"]
    flops = costs.train_flops_per_token(model, run.counters["seq_len"])
    peak = costs.peaks(run.device_kind)["bf16_flops"]
    return flops * tps / (peak * run.device_count) * 100.0
