"""Tiny sizes of the benchmark's cells, for the CPU tests: the same
drivers, references and checks, a cluster of tens of nodes and a model
of tens of widths. Importing it puts the checkout's root, ``chipbench``
and ``src`` on the path (the suite's ``conftest`` is the repository's)."""
from __future__ import annotations

import contextlib
import io
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT / "chipbench", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def fleet(config, traffic):
    config = dict(config, n_workers=24, precision="float64")
    tenants = traffic.get("tenants", config["max_running_apps"])
    traffic = dict(traffic, tenants=min(tenants, 6),
                   warmup_sim_s=40.0,
                   warmup_jobs=min(8, traffic.get("warmup_jobs", 0)),
                   warmup_rows=0, warmup_compacted=False,
                   check=dict(traffic["check"], share=1.0))
    if "job" in traffic:
        traffic["job"] = dict(traffic["job"], splits_per_worker=1)
    return config, traffic


def train(config, traffic):
    from repro.configs import get_config, reduced_config
    c = reduced_config(get_config(config["program_arch"]))
    model = dict(config["model"], hidden_size=c.d_model,
                 num_hidden_layers=c.n_layers, num_attention_heads=c.n_heads,
                 num_key_value_heads=c.n_kv_heads, intermediate_size=c.d_ff,
                 vocab_size=c.vocab_size, rope_theta=c.rope_theta,
                 rms_norm_eps=c.norm_eps,
                 tie_word_embeddings=c.tie_embeddings)
    config = dict(config, program_reduced=True, model=model,
                  runtime=dict(config["runtime"], seq_len=16),
                  trainer=dict(config["trainer"], param_dtype="float32",
                               compute_dtype="float32"))
    return config, traffic


TWEAKS = {"fleet": fleet, "train": train}


def tweak_for(workload):
    from chipbench import harness
    m = harness.load_manifest()
    _cell, _config, traffic = harness.find_cell(m, workload)
    return TWEAKS[traffic["driver"]]


def run_cell(workload, *, seed=3_000_000_017, seconds=2.0, trace=0,
             hooks=None):
    """One run of the real harness off-chip; returns (rc, stdout lines,
    last-line JSON or None)."""
    import run as R
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = R.main(["--workload", workload, "--seed", str(seed),
                     "--seconds", str(seconds), "--trace", str(trace)],
                    require_chip=False, hooks=hooks,
                    tweak=tweak_for(workload), cache=False)
    lines = out.getvalue().splitlines()
    last = None
    if lines:
        try:
            last = json.loads(lines[-1])
        except ValueError:
            last = None
    return rc, lines, last
