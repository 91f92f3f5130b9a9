"""A train cell cut to a size the CPU runs in seconds, for the tests.

Widths and depth are the program's smoke presets; the program's registry
entry is replaced for the test by monkeypatching ``repro.models.get_config``.
The limits are set from CPU readings at this size on the seeds the tests
use (2**31 + 12345 and 7), not from the chip's: the program read loss_gap
1.6e-5 to 2.7e-5, grad_gap 3.2e-3 to 6.0e-3 and change_gap 3.5e-3 to
7.5e-3; the float8 control 2.1e-4 to 3.9e-4, 2.3e-2 to 5.9e-2, and
2.7e-2 to 6.4e-2.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

SSM = {"d_state": 16, "d_conv": 4, "expand": 2, "head_dim": 16,
       "n_groups": 1, "chunk_size": 32}
REGISTRY = "mamba2-370m"
MODEL = {
    "family": "ssm", "num_layers": 2, "d_model": 64, "vocab_size": 256,
    "num_heads": 0, "num_kv_heads": 0, "head_dim": 0, "d_ff": 0,
    "tie_embeddings": True, "rms_eps": 1e-05, "act": "silu",
    "dtype": "bfloat16", "rope_theta": 10000.0, "hybrid_attn_every": 0,
    "ssm": SSM}
TRAFFIC = {"kind": "train", "seq_len": 128, "global_batch": 4,
           "mesh": [1, 1], "check_steps": 3, "reference_rows": 2,
           "optimizer": {"name": "adamw", "learning_rate": 0.0015,
                         "warmup_steps": 1, "b1": 0.9, "b2": 0.95,
                         "eps": 1e-08, "weight_decay": 0.1,
                         "grad_clip": 1.0}}
LIMITS = {"loss_gap": 1e-3, "grad_gap": 2e-2, "change_gap": 2e-2}


def program(monkeypatch):
    """Point the program's registry at the smoke model; return the
    configuration file's contents for it."""
    import repro.models
    from repro.configs.base import SSMConfig

    real = repro.models.get_config
    over = {k: MODEL[k] for k in ("num_layers", "d_model", "vocab_size")}
    monkeypatch.setattr(repro.models, "get_config", lambda name: real(
        name).scaled(name=f"{name}-smoke", ssm=SSMConfig(**SSM),
                     remat="none", **over))
    return {"program": {"registry": REGISTRY}, "reference": "mamba2",
            "model": MODEL}


def cell(config: dict, name: str = "smoke.train"):
    from chipbench import harness

    return harness.Cell(name=name, chips=1, config=config, traffic=TRAFFIC,
                        limits=LIMITS, end_to_end=[], per_layer=[],
                        kind=harness.kind("train"))
