"""The ``zamba2-7b.train4`` cell: the harness finds it, its reference lays
out the program's parameters of the cut, and the reference's model
operations per token are the hand count and within the compiled step's."""
from __future__ import annotations

import smoke

from chipbench import harness  # noqa: E402
from chipbench.reference import zamba2  # noqa: E402

CELL = "zamba2-7b.train4"


def test_the_cell_finds_its_files():
    c = harness.cell(CELL)
    assert c.chips == 4 and c.traffic["mesh"] == [4, 1]
    assert c.config["reference"] == "zamba2"
    assert [m["name"] for m in c.end_to_end] == ["train_tokens_per_s",
                                                 "setup_s"]
    assert [m["name"] for m in c.per_layer] == [
        "train_mfu", "device_idle_pct.train", "collective_exposed_pct.train"]


def test_the_program_matches_the_file_and_the_reference_layout():
    from repro.models.params import tree_paths
    from repro.models.transformer import model_specs

    c = harness.cell(CELL)
    cfg = c.kind.program_config(c.config)       # every size the file states
    assert cfg.hybrid_layer_ids == [6, 11] and cfg.num_layers == 12
    got = {k: (tuple(s.shape), s.dtype)
           for k, s in tree_paths(model_specs(cfg)).items()}
    want = {k: (tuple(s), d)
            for k, (s, d, _) in zamba2.layout(c.config["model"]).items()}
    assert got == want
    # the published model is the same structure, 81 layers deep
    pub = c.config["published"]
    full = c.kind.program_config(dict(c.config, program={
        "registry": "zamba2-7b"}, model=dict(
            c.config["model"], num_layers=pub["num_hidden_layers"],
            hybrid_layer_ids=pub["hybrid_layer_ids"])))
    assert [i for i, t in enumerate(pub["layers_block_type"])
            if t == "hybrid"] == full.hybrid_layer_ids


def test_model_flops_are_the_hand_count():
    m = harness.cell(CELL).config["model"]
    d, f, r, v = 3584, 14336, 128, 32000
    di, nh, g, n, p, chunk, seq = 7168, 112, 2, 64, 64, 256, 4096
    heads, hd = 32, 224
    mamba = (2 * d * (2 * di + 2 * g * n + nh)     # in_proj
             + 2 * 4 * (di + 2 * g * n)           # depthwise conv
             + 2 * chunk * n * g                  # C·Bᵀ per group
             + 2 * chunk * p * nh                 # scores · x per head
             + 2 * 2 * n * p * nh                 # chunk states, C · state
             + 2 * di * d)                        # out_proj
    shared = (2 * (2 * d) * 3 * heads * hd         # q, k, v from 2d
              + 2 * heads * hd * d                # o
              + 2 * 2 * heads * hd * (seq + 1) / 2  # causal QKᵀ and PV
              + 2 * 3 * d * f                     # gate, up, down
              + 2 * (d * r + r * 2 * f)           # adapter
              + 2 * d * d)                        # linear
    want = 3 * (12 * mamba + 2 * shared + 2 * d * v)
    got = zamba2.model_flops_per_token(m, seq)
    assert got == want
    assert 11.0e9 < got < 11.2e9


def test_model_flops_match_the_compiled_step():
    """As ``test_flops`` for Mamba-2: the model count falls short of the
    compiled step's (norms, activations, softmax, loss and the update, and
    attention's diagonal blocks in full) by under 15%, and never exceeds
    it."""
    import jax
    import jax.numpy as jnp
    from repro.models import get_smoke_config
    from repro.models.params import abstract_params
    from repro.models.transformer import model_specs
    from repro.train.loop import make_train_step
    from repro.train.optimizer import OptimizerConfig, opt_state_abstract

    seq, batch = 512, 2
    cfg = get_smoke_config("zamba2-7b").scaled(
        d_model=256, head_dim=128, d_ff=1024, adapter_rank=32,
        attn_chunk=128, remat="none", scan_layers=False)
    m = {"d_model": 256, "vocab_size": cfg.vocab_size,
         "num_layers": cfg.num_layers, "num_heads": cfg.num_heads,
         "num_kv_heads": cfg.num_kv_heads, "head_dim": 128, "d_ff": 1024,
         "adapter_rank": 32, "hybrid_layer_ids": cfg.hybrid_layer_ids,
         "ssm": {"d_state": cfg.ssm.d_state, "d_conv": cfg.ssm.d_conv,
                 "expand": cfg.ssm.expand, "head_dim": cfg.ssm.head_dim,
                 "n_groups": cfg.ssm.n_groups,
                 "chunk_size": cfg.ssm.chunk_size}}
    specs = model_specs(cfg)
    rows = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    compiled = jax.jit(make_train_step(cfg, OptimizerConfig())).lower(
        abstract_params(specs), opt_state_abstract(specs, "adamw"),
        {"tokens": rows, "targets": rows}).compile()
    cost = compiled.cost_analysis()
    cost = cost if isinstance(cost, dict) else cost[0]
    model = zamba2.model_flops_per_token(m, seq) * batch * seq
    assert 0.85 <= model / cost["flops"] <= 1.0, (model, cost["flops"])
