"""Zamba2's hybrid block (the ``hybrid`` family) against its plain float32
reference (``chipbench/reference/zamba2.py``) and against transformers'
``Zamba2ForCausalLM``, on seeded random weights at the smoke size: two
shared blocks, per-invocation adapters and linears, irregular hybrid
positions (2, 4, 5 of 6 layers), two groups in the Mamba-2 layers.

Everything here runs in float32 on the CPU, where a float32 product is
exact to float32 rounding, so the program and the references differ by
the order of their sums alone: the SSD core's chunked form against the
paper's minimal listing, attention in query blocks against one block,
and the scan over layers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import zamba2 as ref
from chipbench.reference.mamba2 import flatten
from repro.models import get_smoke_config
from repro.models.params import tree_paths
from repro.models.ssm import gated_rms_norm
from repro.models.common import rms_norm
from repro.models.transformer import decode_step, forward, model_specs, \
    prefill

CFG = get_smoke_config("zamba2-7b").scaled(dtype="float32")
MODEL = {
    "family": "hybrid", "num_layers": CFG.num_layers,
    "d_model": CFG.d_model, "vocab_size": CFG.vocab_size,
    "num_heads": CFG.num_heads, "num_kv_heads": CFG.num_kv_heads,
    "head_dim": CFG.head_dim, "d_ff": CFG.d_ff, "tie_embeddings": True,
    "rms_eps": CFG.rms_eps, "act": "gelu", "dtype": "float32",
    "rope_theta": CFG.rope_theta, "hybrid_layer_ids": CFG.hybrid_layer_ids,
    "num_mem_blocks": CFG.num_mem_blocks, "adapter_rank": CFG.adapter_rank,
    "ssm": {"d_state": CFG.ssm.d_state, "d_conv": CFG.ssm.d_conv,
            "expand": CFG.ssm.expand, "head_dim": CFG.ssm.head_dim,
            "n_groups": CFG.ssm.n_groups,
            "chunk_size": CFG.ssm.chunk_size}}
B, S = 2, 64


def _weights(seed=0):
    return ref.init(jax.random.PRNGKey(seed), MODEL)


def _rows(seed=1):
    t = jax.random.randint(jax.random.PRNGKey(seed), (B, S + 1), 0,
                           CFG.vocab_size)
    return t[:, :-1], t[:, 1:]


def test_layout_is_the_programs_tree():
    got = {k: (tuple(s.shape), s.dtype)
           for k, s in tree_paths(model_specs(CFG)).items()}
    want = {k: (tuple(s), d) for k, (s, d, _) in ref.layout(MODEL).items()}
    assert got == want


@pytest.mark.parametrize("remat,block", [("none", 1024), ("full", 16)])
def test_loss_and_grads_match_the_reference(remat, block, monkeypatch):
    # the reference's blocks of positions: one block, and four
    monkeypatch.setattr(ref, "BLOCK", block)
    cfg = CFG.scaled(remat=remat)
    params = _weights()
    tok, tgt = _rows()
    loss, grads = jax.jit(jax.value_and_grad(lambda p: forward(
        cfg, p, {"tokens": tok, "targets": tgt})[0]))(params)
    with jax.default_matmul_precision("highest"):
        rloss, rgrads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(MODEL, p, tok, tgt)))(params)
    # float32 sums in other orders: 1e-5 of the loss
    assert abs(float(loss) - float(rloss)) < 1e-5 * abs(float(rloss))
    flat, rflat = flatten(grads), flatten(rgrads)
    assert set(flat) == set(rflat)
    for k in rflat:
        g, r = np.asarray(flat[k]), np.asarray(rflat[k])
        # every leaf, both shared blocks and both adapters included, has
        # a gradient; 1e-3 of the leaf's largest entry covers the sum
        # orders of 64 positions through six layers
        assert np.abs(r).max() > 0, k
        assert np.abs(g - r).max() <= 1e-3 * np.abs(r).max(), k


def test_prefill_then_decode_matches_the_forward_logits():
    params = _weights()
    tok, tgt = _rows()
    _, logits = jax.jit(lambda p: forward(
        CFG, p, {"tokens": tok, "targets": tgt}))(params)
    p0 = S // 2
    last, cache = jax.jit(lambda p, t: prefill(CFG, p, {"tokens": t}, S))(
        params, tok[:, :p0])
    step = jax.jit(lambda p, c, t: decode_step(CFG, p, c, {"tokens": t}))
    got = [last]
    for i in range(p0, S - 1):
        out, cache = step(params, cache, tok[:, i:i + 1])
        got.append(out)
    got = np.stack([np.asarray(g) for g in got], axis=1)
    want = np.asarray(logits[:, p0 - 1:S - 1])
    assert int(cache["index"]) == S - 1
    # the recurrent update and the cache against the chunked sequence
    # form, float32: 1e-4 of the largest logit
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def test_gated_norm_per_group():
    rng = np.random.default_rng(0)
    y, z = (jnp.asarray(rng.standard_normal((2, 3, 32)), jnp.float32)
            for _ in range(2))
    w = jnp.asarray(rng.uniform(0.5, 1.5, 32), jnp.float32)
    # one group is the norm over all channels, bit for bit
    assert np.array_equal(np.asarray(gated_rms_norm(y, z, w, 1, 1e-5)),
                          np.asarray(rms_norm(y * jax.nn.silu(z), w, 1e-5)))
    x = np.asarray(y * jax.nn.silu(z), np.float64).reshape(2, 3, 2, 16)
    want = (x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5)).reshape(
        2, 3, 32) * np.asarray(w)
    got = np.asarray(gated_rms_norm(y, z, w, 2, 1e-5))
    assert np.abs(got - want).max() < 1e-5
    # and it differs from the norm over all channels
    assert np.abs(got - np.asarray(gated_rms_norm(y, z, w, 1, 1e-5))).max() \
        > 1e-2


def _hf_model(params):
    """transformers' Zamba2ForCausalLM at the smoke size with ``params``
    copied in."""
    torch = pytest.importorskip("torch")
    tr = pytest.importorskip("transformers")
    ids = CFG.hybrid_layer_ids
    conf = tr.Zamba2Config(
        vocab_size=CFG.vocab_size, hidden_size=CFG.d_model,
        num_hidden_layers=CFG.num_layers,
        layers_block_type=["hybrid" if i in ids else "mamba"
                           for i in range(CFG.num_layers)],
        mamba_d_state=CFG.ssm.d_state, mamba_d_conv=CFG.ssm.d_conv,
        mamba_expand=CFG.ssm.expand, mamba_ngroups=CFG.ssm.n_groups,
        n_mamba_heads=CFG.ssm.d_inner(CFG.d_model) // CFG.ssm.head_dim,
        # transformers' CPU path passes states between chunks summed over
        # the wrong axis (the destination chunk's), so it is right on one
        # chunk only: it takes the 64 positions as one, the program as two
        chunk_size=S, intermediate_size=CFG.d_ff,
        hidden_act="gelu", num_attention_heads=CFG.num_heads,
        num_mem_blocks=CFG.num_mem_blocks, adapter_rank=CFG.adapter_rank,
        use_mem_rope=True, rope_theta=CFG.rope_theta,
        rms_norm_eps=CFG.rms_eps, tie_word_embeddings=True,
        # transformers' CPU path clamps dt below at time_step_min; the
        # mamba_ssm kernels Zamba2 runs do not, nor does the program
        time_step_min=1e-30, attn_implementation="eager")
    assert conf.attention_head_dim == CFG.head_dim
    assert S > CFG.ssm.chunk_size
    model = tr.Zamba2ForCausalLM(conf).float().eval()
    t = lambda a: torch.tensor(np.asarray(a, np.float32))
    sd = {"model.embed_tokens.weight": t(params["embed"]),
          "model.final_layernorm.weight": t(params["final_norm"])}
    lp, sh, hy = params["layers"], params["shared"], params["hybrid"]
    d, hq, hd = CFG.d_model, CFG.num_heads, CFG.head_dim
    for i in range(CFG.num_layers):
        pre = f"model.layers.{i}."
        if i in ids:
            k = ids.index(i)
            b = k % CFG.num_mem_blocks
            sd[pre + "linear.weight"] = t(hy["linear"][k].T)
            st = pre + "shared_transformer."
            sd[st + "input_layernorm.weight"] = t(sh["ln_attn"][b])
            sd[st + "pre_ff_layernorm.weight"] = t(sh["ln_mlp"][b])
            for n in ("q", "k", "v"):
                sd[st + f"self_attn.{n}_proj.weight"] = t(
                    sh["attn"][f"w{n}"][b].reshape(2 * d, hq * hd).T)
            sd[st + "self_attn.o_proj.weight"] = t(
                sh["attn"]["wo"][b].reshape(hq * hd, d).T)
            mp = sh["mlp"]
            ff = st + "feed_forward."
            sd[ff + "gate_up_proj.weight"] = t(np.concatenate(
                [mp["w_gate"][b], mp["w_up"][b]], axis=1).T)
            sd[ff + "down_proj.weight"] = t(mp["w_down"][b].T)
            sd[ff + f"gate_up_proj_adapter_list.{k}.0.weight"] = t(
                hy["lora_a"][k].T)
            sd[ff + f"gate_up_proj_adapter_list.{k}.1.weight"] = t(
                hy["lora_b"][k].T)
            pre += "mamba_decoder."
        ssm = jax.tree.map(lambda x: x[i], lp["ssm"])
        sd[pre + "input_layernorm.weight"] = t(lp["ln"][i])
        mx = pre + "mamba."
        sd[mx + "in_proj.weight"] = t(ssm["in_proj"].T)
        sd[mx + "conv1d.weight"] = t(ssm["conv_w"].T[:, None, :])
        sd[mx + "conv1d.bias"] = t(ssm["conv_b"])
        sd[mx + "dt_bias"] = t(ssm["dt_bias"])
        sd[mx + "A_log"] = t(ssm["a_log"])
        sd[mx + "D"] = t(ssm["d_skip"])
        sd[mx + "norm.weight"] = t(ssm["out_norm"])
        sd[mx + "out_proj.weight"] = t(ssm["out_proj"].T)
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected, unexpected
    # what is left is the tied head and the shared blocks' weights as
    # other hybrid layers (and their adapter lists) see them
    assert all(k == "lm_head.weight" or ".shared_transformer." in k
               for k in missing), missing
    model.tie_weights()
    return model, torch


def test_logits_match_transformers_zamba2():
    params = _weights()
    model, torch = _hf_model(params)
    for i in CFG.hybrid_layer_ids:     # the blocks are shared in torch too
        assert model.model.layers[i].shared_transformer is \
            model.model.layers[CFG.hybrid_layer_ids[
                CFG.hybrid_layer_ids.index(i) % CFG.num_mem_blocks]] \
            .shared_transformer
    tok, tgt = _rows()
    _, logits = jax.jit(lambda p: forward(
        CFG, p, {"tokens": tok, "targets": tgt}))(params)
    with torch.no_grad():
        want = model(torch.tensor(np.asarray(tok))).logits.numpy()
    got = np.asarray(logits)
    # float32 on both sides, sums in other orders: 1e-4 of the largest
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()
