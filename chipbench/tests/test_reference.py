"""The plain reference's state-space map against the recurrence it
stands for, one time step after another in NumPy float64."""
from __future__ import annotations

import numpy as np

import smoke  # noqa: F401

from chipbench.reference import mamba2  # noqa: E402


def recurrence(x, dt, a, b, c):
    bsz, s, h, p = x.shape
    rep = h // b.shape[2]
    b = np.repeat(b, rep, axis=2)
    c = np.repeat(c, rep, axis=2)
    state = np.zeros((bsz, h, p, b.shape[-1]))
    y = np.zeros_like(x)
    for t in range(s):
        state = state * np.exp(dt[:, t] * a)[..., None, None] \
            + (dt[:, t, :, None] * x[:, t])[..., None] * b[:, t, :, None, :]
        y[:, t] = np.einsum("bhpn,bhn->bhp", state, c[:, t])
    return y


def test_ssd_is_the_recurrence():
    rng = np.random.default_rng(0)
    bsz, s, h, p, g, n = 2, 96, 4, 8, 2, 16
    x = rng.standard_normal((bsz, s, h, p))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (bsz, s, h)))
    a = -rng.uniform(1, 16, h)
    b = rng.standard_normal((bsz, s, g, n))
    c = rng.standard_normal((bsz, s, g, n))
    want = recurrence(x, dt, a, b, c)
    f32 = lambda t: np.asarray(t, np.float32)
    got = np.asarray(mamba2.ssd(f32(x), f32(dt), f32(a), f32(b), f32(c),
                                block=32))
    # float32 against float64: relative to the output's scale
    assert np.max(np.abs(got - want)) < 1e-4 * np.max(np.abs(want))
