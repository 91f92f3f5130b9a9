#!/usr/bin/env python3
"""Run the predictor and the step it predicts on one TPU v5e.

Everything runs in this one process, which starts no other; a chip
belongs to one process at a time.  Phases, in order:

1. kernels      flash attention and rmsnorm at llama3-1b widths, ssd_scan
                at mamba2-370m widths, compiled for the chip, each against
                its ``ref.py`` oracle;
2. ground truth ``repro.train.train`` on llama3-1b at its published widths
                (16 layers, d_model 2048), seq 2048, batch 1, full
                activation checkpointing, AdamW, random weights from a
                seed: every loss, the median step time after the first
                step, peak device memory;
3. prediction   the same step exported with ``train_step_exports`` (the
                export compiles for the CPU on every host): SHA-256 of its
                StableHLO and optimized HLO, and ``Session.predict`` on
                ``tpu-v5e`` by ``roofline`` and by ``mixed`` (cocossim
                systolic), each with its error against the phase-2 time;
4. profiling    the ``profiling`` tier compiles and runs the raw export's
                regions on the chip: regions, distinct fingerprints, emit
                failures, compile seconds, predicted step time and error.

``--four-chips`` runs instead only the sharded path of a 2x2 host: the
llama3-1b step over a (4, 1) data x model mesh of the four chips at global
batch 8, its step-0 loss against a forward-only loss of the same batch on
one chip, and the ``tpu-v5e`` prediction of that step on a (2, 2) torus,
exported on four CPU devices.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
A failed phase, or a host where JAX finds no TPU, exits non-zero before
it::

    python3 chip_smoke.py
    python3 chip_smoke.py --four-chips

:func:`print_export_hashes`, run in a CPU-only process
(``JAX_PLATFORMS=cpu python3 -c "import chip_smoke as c;
c.print_export_hashes()"``), prints the phase-3 hashes as a host without
an accelerator computes them.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import math
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
SEQ = 2048
BATCH = 1                # batch 2 needs 15.79 of the chip's 15.75 GiB
STEPS = 8
LEARNING_RATE = 3e-3     # constant after one warm-up step, so that a few
WARMUP_STEPS = 1         # steps move the loss of random weights


class PhaseFailed(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailed(what)


def llama3_1b():
    from repro.models import get_config
    return get_config("llama3-1b").scaled(remat="full")


def run_config(cfg, seq: int, batch: int):
    from repro.configs.base import RunConfig, ShapeConfig
    return RunConfig(model=cfg, shape=ShapeConfig("chip-smoke", seq, batch,
                                                  "train"),
                     learning_rate=LEARNING_RATE, warmup_steps=WARMUP_STEPS,
                     seed=SEED)


def rel_err(got, want) -> float:
    """max |got - want| over max |want|, in f32."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


# ------------------------------------------------------------- phase 1

def kernels_phase(*, attn=(2, 32, 8, SEQ, 64), rms=(16384, 2048),
                  ssd=(1, SEQ, 32, 64, 1, 128, 256), tol: float = 2e-2):
    """Each Pallas kernel against its oracle; ``attn`` is (batch, query
    heads, kv heads, seq, head dim), ``rms`` (rows, d_model), ``ssd``
    (batch, seq, heads, head dim, groups, state, chunk)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.rmsnorm.ops import rmsnorm
    from repro.kernels.rmsnorm.ref import rmsnorm_ref
    from repro.kernels.ssd_scan.ref import ssd_ref
    from repro.models.ssm import ssd_chunked

    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))
    bf16 = jnp.bfloat16

    def normal(shape, dtype=bf16):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    errs = {}
    with jax.default_matmul_precision("highest"):
        b, hq, hkv, s, d = attn
        q, k, v = (normal((b, hq, s, d)), normal((b, hkv, s, d)),
                   normal((b, hkv, s, d)))
        group = hq // hkv
        errs["flash_attention"] = rel_err(
            flash_attention(q, k, v, causal=True),
            attention_ref(q, jnp.repeat(k, group, axis=1),
                          jnp.repeat(v, group, axis=1), causal=True))

        x, w = normal(rms), normal(rms[-1:])
        errs["rmsnorm"] = rel_err(rmsnorm(x, w), rmsnorm_ref(x, w))

        b, s, h, p, g, n, chunk = ssd
        x = normal((b, s, h, p))
        dt = jax.nn.softplus(normal((b, s, h), jnp.float32) - 2.0)
        a = -jnp.exp(normal((h,), jnp.float32) * 0.5)
        bi, ci = normal((b, s, g, n)), normal((b, s, g, n))
        y, state = ssd_chunked(x, dt, a, bi, ci, chunk, kernel=True)
        y_ref, state_ref = ssd_ref(x, dt, a, bi, ci)
        errs["ssd_scan"] = max(rel_err(y, y_ref), rel_err(state, state_ref))
    for name, err in errs.items():
        print(f"kernel {name}: max error {err:.3e} of the oracle's range "
              f"(limit {tol})")
        check(err < tol, f"kernel {name} disagrees with its oracle: {err}")
    return errs


# ------------------------------------------------------------- phase 2

def train_phase(cfg, *, seq: int = SEQ, batch: int = BATCH,
                steps: int = STEPS, mesh=None) -> dict:
    """The ground-truth step through ``repro.train.train``."""
    import jax

    from repro.train import train

    run = run_config(cfg, seq, batch)
    res = train(run, mesh=mesh, num_steps=steps, log_every=1)
    losses = res.losses
    print(f"losses: {losses}")
    check(len(losses) == steps and all(math.isfinite(x) for x in losses),
          f"losses not all finite: {losses}")
    head = statistics.mean(losses[:3])
    tail = statistics.mean(losses[-3:])
    check(tail < head, f"loss did not fall: first three {head}, "
                       f"last three {tail}")
    step_s = statistics.median(res.step_times[1:])
    print(f"median step time after the first step: {step_s * 1e3:.3f} ms "
          f"(first step, compile included: {res.step_times[0]:.3f} s)")
    for d in (mesh.devices.flat if mesh is not None else jax.devices()[:1]):
        peak = (d.memory_stats() or {}).get("peak_bytes_in_use")
        print(f"peak device memory {d}: " + (
            "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"))
    return {"losses": losses, "step_s": step_s, "run": run}


# ------------------------------------------------------------- phase 3

def export_step(cfg, seq: int, batch: int, run, mesh=None):
    """The phase-2 step as the predictor sees it (compiled for the CPU)."""
    import contextlib

    from repro.core.pipeline import export_workload
    from repro.train.loop import optimizer_config, train_step_exports

    t0 = time.perf_counter()
    jitted, args = train_step_exports(cfg, seq, batch, mesh,
                                      opt_cfg=optimizer_config(run))
    with mesh if mesh is not None else contextlib.nullcontext():
        w = export_workload(jitted, *args, name=f"{cfg.name}-train")
    print(f"export: {time.perf_counter() - t0:.3f} s")
    return w


def export_hashes(w) -> dict:
    return {kind: hashlib.sha256(text.encode()).hexdigest()
            for kind, text in (("stablehlo", w.stablehlo_text),
                               ("hlo", w.hlo_text))}


def print_export_hashes(cfg=None, seq: int = SEQ, batch: int = BATCH):
    """Phase 3's export and its hashes, without the chip."""
    cfg = cfg or llama3_1b()
    w = export_step(cfg, seq, batch, run_config(cfg, seq, batch))
    for kind, digest in export_hashes(w).items():
        print(f"export sha256 {kind} {digest}")


def predict_phase(w, measured_s: float, session, **predict_kw) -> dict:
    out = {}
    for est in ("roofline", "mixed"):
        t0 = time.perf_counter()
        p = session.predict(w, system="tpu-v5e", estimator=est,
                            **predict_kw)
        out[est] = p.step_time_s
        label = "mixed (cocossim)" if est == "mixed" else est
        print(f"predict {label} on tpu-v5e: {p.step_time_s * 1e3:.3f} ms, "
              f"error {(p.step_time_s - measured_s) / measured_s:+.1%} vs "
              f"measured {measured_s * 1e3:.3f} ms "
              f"(prediction took {time.perf_counter() - t0:.3f} s)")
        check(p.step_time_s > 0, f"{est} predicted {p.step_time_s}")
    return out


# ------------------------------------------------------------- phase 4

def profiling_phase(w, measured_s: float, session) -> float:
    """The ``profiling`` tier on the process's device."""
    from repro.core.estimators import ProfilingEstimator

    plan = session.plan(w, fidelity="raw")
    est = ProfilingEstimator(program=plan.program, runs=3,
                             target_system=session.systems.get("tpu-v5e"))
    t0 = time.perf_counter()
    p = session.predict(plan, system="tpu-v5e", estimator=est)
    wall = time.perf_counter() - t0
    print(f"profiling on {est.device.platform} {est.device.device_kind!r} "
          f"as {est.system.name!r}, projected: {est.target_system is not None}")
    print(f"profiling: {len(plan.compute_regions)} regions, "
          f"{len(plan.fingerprints)} distinct fingerprints, "
          f"{est.emit_failures} emit failures, compile "
          f"{est.compile_seconds:.3f} s, wall {wall:.3f} s; a compile or "
          "execute failure raises, so none occurred")
    print(f"predict profiling on tpu-v5e: {p.step_time_s * 1e3:.3f} ms, "
          f"error {(p.step_time_s - measured_s) / measured_s:+.1%} vs "
          f"measured {measured_s * 1e3:.3f} ms")
    check(p.step_time_s > 0, f"profiling predicted {p.step_time_s}")
    return p.step_time_s


# ------------------------------------------------------------ four chips

def reference_loss(cfg, run) -> float:
    """Forward-only loss of the step-0 batch, with the initial weights,
    on one chip."""
    import jax

    from repro.models.params import init_params
    from repro.models.transformer import forward, model_specs
    from repro.train.data import DataConfig, SyntheticSource

    params = init_params(model_specs(cfg), jax.random.PRNGKey(run.seed))
    batch = next(SyntheticSource(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=run.shape.seq_len,
        global_batch=run.shape.global_batch, seed=run.seed)))
    return float(jax.jit(lambda p, b: forward(cfg, p, b)[0])(params, batch))


def four_chip_phase(cfg, *, seq: int = SEQ, batch: int = 8,
                    steps: int = STEPS, tol: float = 2e-2) -> None:
    import jax

    from repro.api import Session
    from repro.launch.mesh import make_mesh

    check(len(jax.devices()) == 4, f"--four-chips needs 4 devices, "
                                   f"have {jax.devices()}")
    run = run_config(cfg, seq, batch)
    ref = reference_loss(cfg, run)
    print(f"forward-only step-0 loss on one chip: {ref}")
    mesh = make_mesh((4, 1), ("data", "model"), devices=jax.devices())
    res = train_phase(cfg, seq=seq, batch=batch, steps=steps, mesh=mesh)
    err = abs(res["losses"][0] - ref)
    print(f"sharded step-0 loss {res['losses'][0]} vs one chip {ref}: "
          f"difference {err:.3e} (limit {tol})")
    check(err < tol, f"sharded step-0 loss is off by {err}")
    w = export_step(cfg, seq, batch, res["run"],
                    mesh=make_mesh((4, 1), ("data", "model")))
    predict_phase(w, res["step_s"], Session(), topology="torus",
                  topology_params={"dims": [2, 2]})


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded step on a 2x2 host")
    args = ap.parse_args(argv)

    import jax

    if args.four_chips:
        # the export of the sharded step needs four CPU devices
        jax.config.update("jax_num_cpu_devices", 4)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (default device {dev}); "
              "nothing was run", file=sys.stderr)
        return 2
    from repro.core.catalog import system_id_for_device
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {dev.platform} {dev.device_kind!r} x{len(jax.devices())}"
          f" -> catalog {system_id_for_device(dev)!r}; compile cache "
          f"{enable_compile_cache()}")
    t0 = time.perf_counter()
    cfg = llama3_1b()
    if args.four_chips:
        four_chip_phase(cfg)
    else:
        from repro.api import Session

        kernels_phase()
        ground = train_phase(cfg)
        w = export_step(cfg, SEQ, BATCH, ground["run"])
        for kind, digest in export_hashes(w).items():
            print(f"export sha256 {kind} {digest}")
        session = Session()
        predict_phase(w, ground["step_s"], session)
        profiling_phase(w, ground["step_s"], session)
    print(f"all phases passed in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
