"""Faults and controls planted under the timed path, to show that ``correct``
catches them.

Each is a context manager that replaces one entry point of the program while
it is active and restores it after.  A fault breaks the entry's answer; a
control puts the plain reference, computed one precision below the
configuration's (three-pass bfloat16 for float32 at full precision), in the
entry's place.  A witness is no fault: it runs the program itself under a
setting that says where a gap to the reference comes from.  The tests run a
whole tiny cell under each fault and control, and
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


def rounds_window_state_altered():
    """After the driver's three set-up rounds, every round returns the
    sources' W_RF 0.1% high."""
    from repro.federated.engine import BatchedRoundEngine

    real = BatchedRoundEngine.round

    def broken(self, *a, **kw):
        out = real(self, *a, **kw)
        self._fault_rounds = getattr(self, "_fault_rounds", 0) + 1
        if self._fault_rounds <= 3:
            return out
        sp = {**out[0], "w_rf": out[0]["w_rf"] * 1.001}
        return (sp, *out[1:])

    return _patched(BatchedRoundEngine, "round", broken)


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


def rounds_control():
    """Every round of the batched plane answers with the reference round in
    three-pass bfloat16: from the trainer's state, on the batches the trainer
    drew and under the round's plan, its Omega drawn by the reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.lib import refs
    from repro.federated.protocol import FedRFTCATrainer

    def control(self, t, plan):
        k = self.k
        batch = self._round_batch()
        if batch["xs"].shape[0] != 1:
            raise ValueError("the reference round takes one local step")
        tm = jax.tree_util.tree_map
        so, to = self._src_opt_stack, self.tgt_opt
        src = [tm(lambda a, i=i: a[i], self._src_stack) for i in range(k)]
        src_opt = [{"step": int(np.asarray(so.step)[i]), "mu": tm(lambda a, i=i: a[i], so.mu),
                    "nu": tm(lambda a, i=i: a[i], so.nu)} for i in range(k)]
        tgt_opt = {"step": int(np.asarray(to.step)), "mu": to.mu, "nu": to.nu}
        ref_batch = {"xs": list(batch["xs"][0]), "ys": list(batch["ys"][0]),
                     "x_msg": list(batch["x_msg"]), "xt": batch["xt_steps"][0],
                     "xt_msg": batch["xt_msg"]}
        src, src_opt, tgt, tgt_opt, _, _ = refs.fed_round_from(
            self.cfg, self.proto, src, src_opt, self.tgt_params, tgt_opt, ref_batch, t, plan,
            precision="high")

        def stack(*xs):
            return jnp.stack(xs)

        self._src_stack = tm(stack, *src)
        self._src_opt_stack = so._replace(
            step=jnp.asarray([o["step"] for o in src_opt], so.step.dtype),
            mu=tm(stack, *[o["mu"] for o in src_opt]), nu=tm(stack, *[o["nu"] for o in src_opt]))
        self.tgt_params = tgt
        self.tgt_opt = to._replace(step=jnp.asarray(tgt_opt["step"], to.step.dtype),
                                   mu=tgt_opt["mu"], nu=tgt_opt["nu"])

    return _patched(FedRFTCATrainer, "_round_batched", control)


def rounds_at_highest():
    """Not a fault: the program's own round, traced under JAX's default
    matmul precision "highest", so that every contraction that asks for no
    precision of its own runs in float32.  The witness that a round's gap to
    the reference is the precision of those contractions."""
    import jax

    return jax.default_matmul_precision("highest")


CONTROLS = {"fit": fit_control, "rounds": rounds_control, "serve": serve_control}

WITNESSES = {"rounds": {"highest": rounds_at_highest}}

FAULTS = {
    "fit": {"eigenvalue_altered": fit_eigenvalue_altered,
            "aligner_column_altered": fit_aligner_column_altered},
    "rounds": {"state_unchanged": rounds_state_unchanged, "half_batch": rounds_half_batch,
               "window_state_altered": rounds_window_state_altered},
    "serve": {"answer_altered": serve_answer_altered},
}
