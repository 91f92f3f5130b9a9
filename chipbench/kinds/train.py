"""Generator of the ``train`` traffic kind: ground-truth training steps
of the system under test, the way ``repro.train.loop.train`` runs them
(the jitted ``make_train_step`` with its state donated, one step after
another, each ended by ``block_until_ready`` and a read of the loss), on
a ``mesh`` of ``[data, model]`` chips.  The benchmark makes the weights
from the seed (its reference's ``init``, jitted with the program's
shardings) and the token rows from the seed and the step number; every
row differs.  Set-up compiles the step and drives the first
``check_steps`` steps through the same call and feed as the window,
reading the numbers the check compares: each step's loss, the first
gradient as the optimizer holds it (AdamW's first moment after one step
is ``(1 - b1)`` times the clipped gradient) and each leaf's change over
those steps.  Then the window runs steps for ``seconds``.  After the
window the program's state is freed and the plain reference follows the
same steps from the same weights and rows.

Parameters: ``seq_len``, ``global_batch``, ``mesh`` ([data, model]),
``check_steps``, ``optimizer`` (the program's ``OptimizerConfig`` fields,
which the reference follows too), ``reference_rows`` (the rows of one
block of the reference's gradient, so that it fits).
"""
from __future__ import annotations

import contextlib
import gc
import math
import statistics
import time
from dataclasses import dataclass, field

from chipbench import harness

#: numbers a train cell's check can compare; its limits file names those
#: it compares, each with its limit
CHECKS = ("loss_gap", "grad_gap", "change_gap")
#: end-to-end metrics a train cell measures, besides ``setup_s``
END_TO_END = ("train_tokens_per_s",)
#: a leaf whose reference gradient is under this share of the median
#: leaf's is taken to move under Adam by round-off alone: its change is not
#: compared (:func:`still_leaves` names them)
STILL_LEAF = 1e-3


# ------------------------------------------------------------ the program

def program_config(config: dict):
    """The system under test's model configuration, checked against every
    size the configuration file's ``model`` states: each key is the
    program's field of that name, and a nested group the fields of the
    program's group."""
    from repro.models import get_config

    prog = config["program"]
    cfg = get_config(prog["registry"]).scaled(**prog.get("overrides", {}))
    missing = object()

    def field_of(obj, k):
        return obj.get(k, missing) if isinstance(obj, dict) \
            else getattr(obj, k, missing)

    wrong = {}
    for k, want in config["model"].items():
        got = field_of(cfg, k)
        if isinstance(want, dict) and got is not missing:
            got = {sub: field_of(got, sub) for sub in want}
        if got != want:
            wrong[k] = (want, "absent" if got is missing else got)
    if wrong:
        raise harness.BenchError(
            f"the program's {prog['registry']} differs from the configuration"
            f" file (file, program): {wrong}")
    return cfg


@dataclass
class Program:
    """The compiled step with its state, as set-up hands it to the window."""
    step_fn: object
    feed: object
    make_weights: object
    params: object
    opt_state: object
    key: object
    mesh: object
    n_done: int = 0
    losses: list = field(default_factory=list)

    def context(self):
        return self.mesh if self.mesh is not None \
            else contextlib.nullcontext()

    def step(self):
        """One step of the window's own call and feed; returns its loss."""
        import jax
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("chipbench:feed"):
            batch = self.feed(self.key, self.n_done)
        with TraceAnnotation("chipbench:step"), self.context():
            self.params, self.opt_state, metrics = jax.block_until_ready(
                self.step_fn(self.params, self.opt_state, batch))
        with TraceAnnotation("chipbench:loss"):
            loss = float(metrics["loss"])
        self.n_done += 1
        self.losses.append(loss)
        return loss


def make_mesh(devices, shape):
    import numpy as np
    from jax.sharding import Mesh

    if math.prod(shape) != len(devices):
        raise harness.BenchError(f"mesh {shape} does not match "
                                 f"{len(devices)} chips")
    if len(devices) == 1:
        return None
    return Mesh(np.array(devices).reshape(shape), ("data", "model"))


def build(config: dict, traffic: dict, seed: int, devices) -> Program:
    """Weights, optimizer state, the jitted step and the feed, placed as
    ``repro.train.loop.train`` places them."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.sharding import ShardingRules, param_sharding
    from repro.models.params import tree_paths
    from repro.models.transformer import model_specs
    from repro.train import loop, optimizer

    cfg = program_config(config)
    ref = harness.reference(config)
    m = config["model"]
    mesh = make_mesh(devices, tuple(traffic["mesh"]))
    rules = ShardingRules()
    specs = tree_paths(model_specs(cfg))
    lay = ref.layout(m)
    got = {k: (tuple(s.shape), s.dtype) for k, s in specs.items()}
    want = {k: (tuple(s), d) for k, (s, d, _) in lay.items()}
    if got != want:
        raise harness.BenchError(f"the program's parameters differ from the "
                                 f"reference's layout: {got} vs {want}")
    if mesh is None:
        p_sh = ref.nest({k: jax.sharding.SingleDeviceSharding(devices[0])
                         for k in specs})
        b_sh = jax.sharding.SingleDeviceSharding(devices[0])
    else:
        p_sh = ref.nest({k: param_sharding(s.axes, mesh, rules, s.shape)
                         for k, s in specs.items()})
        b_sh = NamedSharding(mesh, P("data"))
    make_weights = jax.jit(lambda k: ref.init(k, m), out_shardings=p_sh)
    key = harness.seed_key(seed)
    params = make_weights(key)

    opt_cfg = optimizer.OptimizerConfig(**traffic["optimizer"])
    init_fn, _ = optimizer.make_optimizer(opt_cfg)
    opt_state = init_fn(params, opt_cfg)
    state_shardings = None
    if mesh is not None:
        replicated = NamedSharding(mesh, P())
        opt_state = jax.tree.map(
            lambda x: x if isinstance(x.sharding, NamedSharding)
            else jax.device_put(x, replicated), opt_state)
        state_shardings = jax.tree.map(
            lambda x: x.sharding, (params, opt_state)) + (None,)
    step_fn = jax.jit(loop.make_train_step(cfg, opt_cfg),
                      donate_argnums=(0, 1), out_shardings=state_shardings)

    b, s, v = traffic["global_batch"], traffic["seq_len"], cfg.vocab_size

    def rows(k, i):
        t = jax.random.randint(jax.random.fold_in(k, i), (b, s + 1), 0, v,
                               jnp.int32)
        return {"tokens": t[:, :-1], "targets": t[:, 1:]}

    feed = jax.jit(rows, out_shardings=b_sh)
    return Program(step_fn=step_fn, feed=feed,
                   make_weights=make_weights, params=params,
                   opt_state=opt_state, key=key, mesh=mesh)


def check_steps(prog: Program, traffic: dict) -> dict:
    """Set-up's first steps, through the window's own call and feed, and
    the program's numbers from them."""
    import jax

    from chipbench.reference.adamw import diff_norms, leaf_norms

    b1 = traffic["optimizer"].get("b1", 0.9)
    n = traffic["check_steps"]
    out = {}
    for i in range(n):
        prog.step()
        if i == 0:
            # the clipped first gradient, from AdamW's first moment
            g = jax.jit(lambda mm: leaf_norms(jax.tree.map(
                lambda x: x / (1 - b1), mm)))(prog.opt_state["m"])
            out["grad"] = {k: float(x) for k, x in g.items()}
    p0 = prog.make_weights(prog.key)
    out["change"] = {k: float(x) for k, x in
                     jax.jit(diff_norms)(prog.params, p0).items()}
    del p0
    out["loss"] = list(prog.losses)
    return out


def window(prog: Program, seconds: float) -> dict:
    """Steps until ``seconds`` have passed; the window ends with the step
    that crosses it, and its time is all the time of those steps."""
    from jax.profiler import TraceAnnotation

    first = prog.n_done
    t0 = time.perf_counter()
    with TraceAnnotation("chipbench:window"):
        while True:
            prog.step()
            if time.perf_counter() - t0 >= seconds:
                break
    wall = time.perf_counter() - t0
    return {"steps": prog.n_done - first, "seconds": wall,
            "losses": prog.losses[first:]}


# ---------------------------------------------------------- the reference

def reference_steps(config: dict, traffic: dict, seed: int, devices,
                    dot=None, rows=None) -> dict:
    """The plain reference over the check's steps, from the same weights
    and rows.  ``dot`` replaces the reference's matrix product (the
    control); ``rows`` keeps only that slice of each batch (a fault)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from chipbench.reference import adamw

    ref = harness.reference(config)
    m = config["model"]
    opt = dict(b1=0.9, b2=0.95, eps=1e-8)
    opt.update(traffic["optimizer"])
    dot = dot or ref.highest_dot
    key = harness.seed_key(seed)
    mesh = None if len(devices) == 1 else make_mesh(
        devices, (len(devices), 1))
    if mesh is None:
        put = None
        b_sh = None
    else:
        # each leaf split over all chips on its largest dimension that
        # they divide; rows split over the chips
        def put(shape):
            dims = [i for i in sorted(range(len(shape)),
                                      key=lambda i: -shape[i])
                    if shape[i] % len(devices) == 0]
            spec = [None] * len(shape)
            if dims:
                spec[dims[0]] = ("data", "model")
            return NamedSharding(mesh, P(*spec))
        b_sh = NamedSharding(mesh, P(("data", "model")))
    lay = ref.layout(m)
    p_sh = None if put is None else ref.nest(
        {k: put(s) for k, (s, _, _) in lay.items()})
    params = jax.jit(lambda k: ref.init(k, m), out_shardings=p_sh)(key)
    b, s = traffic["global_batch"], traffic["seq_len"]

    def batch(i):
        t = jax.random.randint(jax.random.fold_in(key, i), (b, s + 1), 0,
                               m["vocab_size"], jnp.int32)
        t = t if rows is None else t[rows]
        return t[:, :-1], t[:, 1:]

    feed = jax.jit(batch, out_shardings=b_sh)
    loss_fn = lambda p, tok, tgt: ref.loss(m, p, tok, tgt, dot)
    st_sh = None if p_sh is None else {"m": p_sh, "v": p_sh}
    init = jax.jit(adamw.init_state, out_shardings=st_sh)
    step = jax.jit(lambda p, st, t, tok, tgt: adamw.step(
        loss_fn, opt, p, st, t, tok, tgt, traffic["reference_rows"]),
        donate_argnums=(0, 1),
        out_shardings=None if p_sh is None else (None, None, p_sh, st_sh))
    p0 = params
    state = init(params)
    params = jax.tree.map(jnp.copy, params)
    losses, grad = [], None
    with jax.default_matmul_precision("highest"):
        for i in range(traffic["check_steps"]):
            loss, gn, params, state = step(params, state, i + 1, *feed(i))
            losses.append(float(loss))
            if i == 0:
                grad = {k: float(x) for k, x in gn.items()}
    change = {k: float(x) for k, x in
              jax.jit(adamw.diff_norms)(params, p0).items()}
    return {"loss": losses, "grad": grad, "change": change}


def still_leaves(ref: dict) -> dict:
    """The leaves left out of change_gap, each with its reference gradient
    as a share of the median leaf's."""
    gmed = statistics.median(ref["grad"].values())
    return {k: g / gmed for k, g in ref["grad"].items()
            if g < STILL_LEAF * gmed}


def compare(prog: dict, ref: dict) -> dict:
    """The three numbers a train cell's check compares.

    loss_gap: the largest relative gap of a step's loss; grad_gap and
    change_gap: over leaves, the gap between the program's norm and the
    reference's, over the larger of the reference's norm of that leaf and
    of the median leaf.  Leaves whose reference gradient is under
    ``STILL_LEAF`` of the median leaf's are left out of change_gap."""
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(prog["loss"],
                                                       ref["loss"]))

    def worst(p, r, keys):
        med = statistics.median(r[k] for k in r)
        return max(abs(p[k] - r[k]) / max(r[k], med, 1e-30) for k in keys)

    still = still_leaves(ref)
    moving = [k for k in ref["grad"] if k not in still]
    return {"loss_gap": loss_gap,
            "grad_gap": worst(prog["grad"], ref["grad"], ref["grad"]),
            "change_gap": worst(prog["change"], ref["change"], moving)}


def run(cell, seed: int, seconds: float, trace: bool, devices) -> dict:
    """One run of a train cell: set-up, window, reference, check."""
    from chipbench import trace as tr

    traffic = cell.traffic
    prog = build(cell.config, traffic, seed, devices)
    numbers = check_steps(prog, traffic)
    tracer = tr.Tracer() if trace else None
    if tracer:
        tracer.start()
    t_window = time.perf_counter()
    win = window(prog, seconds)
    summary = None
    if tracer:
        summary = tr.reduce(tracer.stop())
    device = harness.device_info(devices)
    prog.params = prog.opt_state = None
    del prog
    gc.collect()
    ref = reference_steps(cell.config, traffic, seed, devices)
    checks = compare(numbers, ref)
    tokens = win["steps"] * traffic["global_batch"] * traffic["seq_len"]
    rate = tokens / win["seconds"]
    return {
        "t_window": t_window,
        "attempted": win["steps"],
        "failed": sum(not math.isfinite(x) for x in win["losses"]),
        "tokens": tokens,
        "window_s": win["seconds"],
        "rate": rate,
        "end_to_end": {"train_tokens_per_s": rate},
        "notes": [f"left out of change_gap (reference gradient over the "
                  f"median leaf's): {still_leaves(ref)}"],
        "checks": {k: (checks[k], lim) for k, lim in cell.limits.items()},
        "device": device,
        "summary": summary,
        "program": numbers,
        "reference": ref,
    }
