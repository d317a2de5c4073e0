"""Faults and controls planted under the timed path, to show that ``correct``
catches them.

Each is a context manager that replaces one entry point of the program while
it is active and restores it after.  A fault breaks the entry's answer; a
control puts the plain reference, computed one precision below the
configuration's (three-pass bfloat16 for float32 at full precision), in the
entry's place.  The tests run a whole tiny cell under each, and
``control.py --plant <name>`` runs whole cells under one at a cell's own size
on the chip.  The benchmark's own runs never use them.
"""
from __future__ import annotations

import contextlib
import sys
from types import SimpleNamespace


@contextlib.contextmanager
def _patched(owner, name, value):
    old = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield
    finally:
        setattr(owner, name, old)


def fit_eigenvalue_altered():
    """The fit's top eigenvalue comes out 0.1% high."""
    import repro.core.rf_tca  # noqa: F401

    mod = sys.modules["repro.core.rf_tca"]
    real = mod.rf_tca_fit

    def broken(*a, **kw):
        st = real(*a, **kw)
        return st._replace(eigvals=st.eigvals.at[0].multiply(1.001))

    return _patched(mod, "rf_tca_fit", broken)


def fit_aligner_column_altered():
    """The fit's first aligner column is a copy of its second."""
    import repro.core.rf_tca  # noqa: F401

    mod = sys.modules["repro.core.rf_tca"]
    real = mod.rf_tca_fit

    def broken(*a, **kw):
        st = real(*a, **kw)
        return st._replace(w_rf=st.w_rf.at[:, 0].set(st.w_rf[:, 1]))

    return _patched(mod, "rf_tca_fit", broken)


def rounds_state_unchanged():
    """The round returns the state it was given."""
    from repro.federated.engine import BatchedRoundEngine

    return _patched(BatchedRoundEngine, "round",
                    lambda self, sp, so, tp, to, batch, masks, chan_key=None: (sp, so, tp, to))


def rounds_half_batch():
    """Half of every training batch is left out; the means run over the rest."""
    from repro.federated.protocol import FedRFTCATrainer

    real = FedRFTCATrainer._round_batch

    def half(self):
        b = real(self)
        keep = b["xs"].shape[-1] // 2
        return {**b, "xs": b["xs"][..., :keep], "ys": b["ys"][..., :keep],
                "xt_steps": b["xt_steps"][..., :keep]}

    return _patched(FedRFTCATrainer, "_round_batch", half)


def serve_answer_altered():
    """The first answer of every dispatch comes out 0.1% high."""
    from repro.serve.dispatcher import BatchingDispatcher

    real = BatchingDispatcher._dispatch

    def broken(self, entry, batch):
        outs = real(self, entry, batch)
        return [o * 1.001 if i == 0 else o for i, o in enumerate(outs)]

    return _patched(BatchingDispatcher, "_dispatch", broken)


def fit_control():
    """``rf_tca_fit`` answers with the reference fit in three-pass bfloat16:
    the same data, the same Omega (drawn from the fit's seed)."""
    import jax.numpy as jnp

    import repro.core.rf_tca  # noqa: F401
    from chipbench.lib import refs

    mod = sys.modules["repro.core.rf_tca"]

    def control(x_s, x_t, *, n_features, m, seed, **kw):
        omega = refs.gauss_omega(seed, n_features, x_s.shape[0])
        ref = refs.fit_reference(x_s, x_t, omega, m=m, precision="high")
        return SimpleNamespace(w_rf=ref["w_rf"], eigvals=jnp.asarray(ref["eigvals"][:m]))

    return _patched(mod, "rf_tca_fit", control)


def serve_control():
    """Every dispatch answers with the reference transform in three-pass
    bfloat16 of the stored aligner, its Omega drawn from the fused stream."""
    from chipbench.lib import refs
    from repro.serve.dispatcher import BatchingDispatcher

    omegas = {}

    def control(self, entry, batch):
        st = entry.state
        spec = (int(st.fused[0]), st.w_rf.shape[0] // 2, batch[0].x.shape[0])
        if spec not in omegas:
            omegas[spec] = refs.fused_omega(*spec)
        return [refs.transform_columns(st.w_rf, omegas[spec], r.x, precision="high",
                                       width=max(256, r.x.shape[1])) for r in batch]

    return _patched(BatchingDispatcher, "_dispatch", control)


CONTROLS = {"fit": fit_control, "serve": serve_control}

FAULTS = {
    "fit": {"eigenvalue_altered": fit_eigenvalue_altered,
            "aligner_column_altered": fit_aligner_column_altered},
    "rounds": {"state_unchanged": rounds_state_unchanged, "half_batch": rounds_half_batch},
    "serve": {"answer_altered": serve_answer_altered},
}
