"""Batching dispatcher: coalesce concurrent requests into one compiled call.

The serving analogue of the batched round engine's one-dispatch-per-round
trick: N concurrent transform/predict requests against the same cached
aligner become ONE jit-compiled dispatch over their concatenated sample
columns, padded to a *bucketed* batch width so the jit cache sees a small
closed set of shapes.

- **Buckets.**  ``bucket_for(n)`` rounds the total column count up to the
  next power-of-two rung of the ladder ``min_bucket .. max_bucket``; a burst
  larger than ``max_bucket`` is split across several dispatches.  Each rung
  owns its own compiled plane, wrapped in a jit-retrace sentinel
  (``serve.<mode>.b<bucket>``) so the compile cache is pinned: a rung traces
  exactly once, and the bench/smoke gate fails if a shape-unstable argument
  ever defeats it.
- **Validity masks.**  Padding reuses the ragged-batch machinery from
  ``federated.protocol``: ``_cycle_pad`` fills the pad columns by cycling
  real samples (never zeros) and ``_ragged_mask`` marks the valid columns;
  the compiled body multiplies its output by the mask, so pad columns leave
  the dispatch as exact zeros and per-request slices are taken host-side.
- **Telemetry.**  Requests and dispatches are counted in the metrics
  registry, batch sizes in host-side counters for the bench record
  (:meth:`BatchingDispatcher.histogram`), and each dispatch's legs are
  program spans (``repro.obs.span``) carrying its requests, valid columns and
  bucket.  None of it touches array values — telemetry off is bitwise
  identical.
"""
from __future__ import annotations

import collections
import time
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.rf_tca import fused_transform_omega, project_features
from repro.core.rff import rff_features
from repro.federated.protocol import _cycle_pad, _ragged_mask
from repro.obs import metrics, sentinel, span


@dataclass
class Request:
    """One serving request: transform (aligned features) or predict (logits)
    for a column batch ``x`` (p, n) against a cached domain pair."""

    x: Any  # (p, n) sample columns
    key: Any = None  # domain pair (routing; the dispatcher is per-entry)
    mode: str = "transform"  # transform | predict
    id: int = -1
    arrival: float = 0.0  # virtual arrival time (load generator bookkeeping)

    def __post_init__(self):
        if self.mode not in ("transform", "predict"):
            raise ValueError(f"mode must be 'transform' or 'predict', got {self.mode!r}")


def _transform_body(w_rf, omega, x, mask):
    out = project_features(w_rf, rff_features(x, omega))  # (m, bucket)
    return out * mask[None, :]


def _transform_probe_body(w_rf, omega, x, mask):
    """Transform plane with an in-graph moment probe: alongside the served
    output, emit the batch's mean RFF row over *valid* columns — the drift
    monitor's live statistic, computed where the features already live (the
    PR-7 probe pattern: auxiliary outputs, primary output unchanged)."""
    feats = rff_features(x, omega)  # (2N, bucket)
    out = project_features(w_rf, feats)  # (m, bucket)
    moment = (feats * mask[None, :]).sum(axis=1) / jnp.maximum(mask.sum(), 1.0)
    return out * mask[None, :], moment


def _predict_body(w_rf, omega, clf_w, clf_b, x, mask):
    aligned = project_features(w_rf, rff_features(x, omega))  # (m, bucket)
    logits = clf_w.T @ aligned + clf_b[:, None]  # (C, bucket)
    return logits * mask[None, :]


# the leg log's bound: a server whose caller never drains it keeps this many
LEG_LOG_MAX = 65536


class BatchingDispatcher:
    """Coalesces queued requests into bucketed compiled dispatches."""

    def __init__(
        self, *, min_bucket: int = 8, max_bucket: int = 256,
        sentinel_prefix: str = "serve",
    ):
        if min_bucket < 1 or max_bucket < min_bucket:
            raise ValueError(
                f"need 1 <= min_bucket <= max_bucket, got {min_bucket}, {max_bucket}"
            )
        self.min_bucket = min_bucket
        self.max_bucket = max_bucket
        self.sentinel_prefix = str(sentinel_prefix)
        # (mode, bucket) -> jitted plane; each plane has its own sentinel so
        # the retrace gate is per bucket rung, not per dispatcher
        self._planes: dict[tuple[str, int], Any] = {}
        self.pending: list[Request] = []
        self.dispatches = 0
        self.batch_requests: dict[int, int] = {}  # requests/dispatch -> count
        self.batch_columns: dict[int, int] = {}  # bucket width -> count
        # drift wiring: when set, transform dispatches run the probed plane
        # and hand (domain_pair, batch moment, n_valid_cols) to this callable
        self.moment_hook = None
        # (assemble_s, dispatch_s) per dispatch, the newest LEG_LOG_MAX kept
        self._leg_log: collections.deque[tuple[float, float]] = collections.deque(
            maxlen=LEG_LOG_MAX
        )

    def bucket_for(self, n_cols: int) -> int:
        """Smallest power-of-two rung >= n_cols (clamped to the ladder)."""
        b = self.min_bucket
        while b < n_cols and b < self.max_bucket:
            b *= 2
        return b

    def _plane(self, mode: str, bucket: int, *, probe: bool = False):
        key = (mode, bucket, probe)
        plane = self._planes.get(key)
        if plane is None:
            if probe:
                body, suffix = _transform_probe_body, ".probe"
            else:
                body = _transform_body if mode == "transform" else _predict_body
                suffix = ""
            plane = jax.jit(sentinel.wrap(
                f"{self.sentinel_prefix}.{mode}.b{bucket}{suffix}", body
            ))
            self._planes[key] = plane
        return plane

    def submit(self, req: Request) -> None:
        self.pending.append(req)
        metrics().counter("serve.requests").inc(mode=req.mode)

    def _take_batch(self) -> list[Request]:
        """Pop a head-of-line run of same-mode requests filling <= max_bucket
        columns (requests larger than max_bucket dispatch alone, truncated
        to the ladder is a caller error — their columns must fit one rung)."""
        batch: list[Request] = []
        cols = 0
        mode = self.pending[0].mode
        while self.pending and self.pending[0].mode == mode:
            n = int(np.shape(self.pending[0].x)[1])
            if n > self.max_bucket:
                raise ValueError(
                    f"request has {n} columns > max_bucket={self.max_bucket}"
                )
            if batch and cols + n > self.max_bucket:
                break
            batch.append(self.pending.pop(0))
            cols += n
        return batch

    def _dispatch(self, entry, batch: list[Request]) -> list[np.ndarray]:
        """One compiled call over the batch's concatenated columns.

        Its two legs are the spans ``serve.batch_assembly`` (concatenation,
        padding, mask, omega) and ``serve.padded_dispatch``, split into
        ``serve.launch`` (the plane's call, argument upload included),
        ``serve.device_wait`` and ``serve.fetch`` (the copy to the host).
        """
        t0 = time.perf_counter()
        state = entry.state
        n_cols = sum(int(np.shape(r.x)[1]) for r in batch)
        bucket = self.bucket_for(n_cols)
        mode = batch[0].mode
        with span("serve.batch_assembly", requests=len(batch), cols=n_cols, bucket=bucket):
            x = np.concatenate([np.asarray(r.x, np.float32) for r in batch], axis=1)
            x_pad, _ = _cycle_pad(x, None, bucket)
            mask_rows = _ragged_mask([n_cols], bucket)
            mask = (
                np.ones((bucket,), np.float32)
                if mask_rows is None
                else np.asarray(mask_rows[0])
            )
            omega = state.omega
            if omega is None:
                omega = fused_transform_omega(state, x.shape[0])
        probe = self.moment_hook is not None and mode == "transform"
        t1 = time.perf_counter()
        moment = None
        with span("serve.padded_dispatch"):
            with span("serve.launch"):
                if mode == "predict":
                    if entry.classifier is None:
                        raise ValueError("predict request against an entry with no classifier")
                    out = self._plane(mode, bucket)(
                        state.w_rf, omega, entry.classifier["w"], entry.classifier["b"],
                        x_pad, mask,
                    )
                elif probe:
                    out, moment = self._plane(mode, bucket, probe=True)(
                        state.w_rf, omega, x_pad, mask
                    )
                else:
                    out = self._plane(mode, bucket)(state.w_rf, omega, x_pad, mask)
            with span("serve.device_wait"):
                out.block_until_ready()
            with span("serve.fetch"):
                out = np.asarray(out)
        t2 = time.perf_counter()
        self._leg_log.append((t1 - t0, t2 - t1))
        self.dispatches += 1
        self.batch_requests[len(batch)] = self.batch_requests.get(len(batch), 0) + 1
        self.batch_columns[bucket] = self.batch_columns.get(bucket, 0) + 1
        metrics().counter("serve.dispatches").inc(mode=mode, bucket=bucket)
        if moment is not None:
            self.moment_hook(batch[0].key, np.asarray(moment), n_cols)
        results, off = [], 0
        for r in batch:
            n = int(np.shape(r.x)[1])
            results.append(out[:, off : off + n])
            off += n
        return results

    def take_legs(self) -> list[tuple[float, float]]:
        """Drain the wall-clock ``(assemble_s, dispatch_s)`` pairs logged
        since the last call (at most the newest ``LEG_LOG_MAX``) — the request
        tracer's processing-leg split."""
        legs = list(self._leg_log)
        self._leg_log.clear()
        return legs

    def flush(self, entry) -> list[tuple[Request, np.ndarray]]:
        """Drain the pending queue against one store entry; returns
        ``(request, result)`` pairs in submission order.  Each head-of-line
        same-mode run becomes one compiled dispatch."""
        done: list[tuple[Request, np.ndarray]] = []
        while self.pending:
            batch = self._take_batch()
            for req, res in zip(batch, self._dispatch(entry, batch)):
                done.append((req, res))
        return done

    def histogram(self) -> dict:
        """JSON-ready batch statistics for the bench record."""
        return {
            "dispatches": self.dispatches,
            "requests_per_dispatch": {
                str(k): v for k, v in sorted(self.batch_requests.items())
            },
            "bucket_widths": {
                str(k): v for k, v in sorted(self.batch_columns.items())
            },
        }
