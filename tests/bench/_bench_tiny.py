"""Tiny cells for the benchmark's CPU tests: the harness's whole path at
widths a test run holds, computed in float32 so that a sound run agrees
with the reference to rounding. Their limits sit between CPU readings of
these sizes: sound runs read upd_norm_gap <= 8e-5 and upd_diff <= 6e-4;
the bfloat16-replica control reads over 30, the half-batch fault 0.11 and
0.42, the exchange left out 0.56 and 0.80."""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), os.path.join(ROOT, "bench")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib.spec import Cell  # noqa: E402

EXPERIMENT = {
    "n_workers": 4, "f_workers": 1, "n_servers": 4, "f_servers": 0,
    "q_workers": 3, "q_servers": 4, "T": 5, "variant": "async",
    "gar": "mda", "pull_gar": "median", "gather_gar": "median",
    "schedule": "constant", "lr0": 0.0005, "protocol_engine": "sharded"}

CONFIGS = {
    "dense": {
        "hidden_size": 128, "intermediate_size": 256,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 512, "rms_norm_eps": 1e-5,
        "rope_theta": 10000.0, "partial_rotary_factor": 1.0,
        "reference": "dense",
        "program": {"arch": "phi4-mini-3.8b", "reduced": True,
                    "act_dtype": "float32"}},
}

LIMITS = {"upd_norm_gap": 0.01, "upd_diff": 0.05, "upd_diff_median": 0.01}


def cell(family: str = "dense", chips: int = 1, limits=None,
         domains: int = 2) -> Cell:
    return Cell(
        name=f"tiny-{family}", chips=chips, config_name=f"tiny_{family}",
        config=CONFIGS[family],
        traffic_name="tiny",
        traffic={"generator": "zipf_rows", "seq": 64, "rows_per_group": 1,
                 "zipf": 1.2, "domains": domains, "pool_epochs": 3},
        settings={"experiment": EXPERIMENT, "trace_epochs": 2,
                  "limits": dict(limits or LIMITS)},
        end_to_end=({"name": "tokens_per_s", "unit": "tokens/s"},
                    {"name": "setup_s", "unit": "s"}),
        per_layer=())
