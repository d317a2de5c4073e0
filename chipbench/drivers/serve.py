"""Driver of ``AlignerServer.serve``: transform requests in an open loop.

Traffic (the cell's ``traffic``): the ``tasks`` (source, target) the server
holds aligners for, ``n_features`` (N), ``m``, the offered ``rate_rps``,
Zipf exponent ``zipf_s`` of task popularity and the request widths,
log-uniform on ``width_lo``..``width_hi`` columns.

Set-up makes the domains, fits one seed-fused aligner per task through
``fit_domain`` on the server's default fused stream, and warms every bucket
of the dispatcher.  The fused programs take the stream's seed as a
compile-time constant, so a stream drawn from the run's seed would compile
them anew in every run; the aligners still differ from seed to seed through
the data.  The schedule is drawn by ``chipbench.lib.traffic``: every seed
sends the same count of requests, the same widths and the same gaps, in
another order.  A request carries target-domain columns of its task (a view
into a host copy of the domain).

Window: a wall-clock open loop.  Whenever requests are due, the
head-of-line task's pending requests (up to the dispatcher's widest bucket
in columns) go to ``AlignerServer.serve`` as one call; otherwise the loop
sleeps until the next arrival.  Each latency runs from the request's due
time to the return of the call that served it, so a late loop counts
against the server.  After the last arrival the queue drains.

Check: a sample of served requests drawn from the seed, the widest among
them, against the plain transform of the stored aligner with the fused
stream's Omega drawn by the reference.
"""
from __future__ import annotations

import resource
import time

import numpy as np

from chipbench.lib import refs
from chipbench.lib.data import derived_seed, make_domains
from chipbench.lib.traffic import head_of_line, open_loop_schedule

CHECK_SAMPLE = 64


def setup(ctx):
    from repro.serve import AlignerServer

    tp = ctx.params
    tasks = [tuple(t) for t in tp["tasks"]]
    names = sorted({d for t in tasks for d in t}, key=list(ctx.config["domains"]).index)
    doms = make_domains(ctx.config, names, ctx.seed)
    srv = AlignerServer(capacity=len(tasks))
    for i, (s, t) in enumerate(tasks):
        srv.fit_domain((s, t), doms[s][0], doms[t][0], n_features=int(tp["n_features"]),
                       m=int(tp["m"]), seed=derived_seed(ctx.seed, 6, i))
    for task in tasks:
        srv.warmup(task)
    _warm_ragged(srv, tasks[0], int(ctx.config["feature_dim"]))
    # host copies of each target domain, doubled so that every width is a view
    pools = {t: np.concatenate([np.asarray(doms[t][0])] * 2, axis=1) for t in {t for _, t in tasks}}
    del doms
    state = {"srv": srv, "tasks": tasks, "pools": pools, "fused_seed": srv.fused_seed,
             "max_cols": srv.dispatcher.max_bucket}
    state.update(requests(state, tp, ctx.seconds, ctx.seed))
    return state


def _warm_ragged(srv, task, p):
    """``AlignerServer.warmup`` sends full buckets only; a batch narrower than
    its bucket takes the masked path, whose programs compile on first use.
    One request one column short of each bucket compiles them in set-up."""
    from repro.serve.dispatcher import Request

    b = srv.dispatcher.min_bucket
    while b <= srv.dispatcher.max_bucket:
        srv.serve([Request(x=np.zeros((p, b - 1), np.float32) + 1.0, key=task)])
        b *= 2


def requests(state, tp, seconds, seed):
    """The window's requests and their due times at the rate ``tp`` states."""
    from repro.serve.dispatcher import Request

    tasks, pools = state["tasks"], state["pools"]
    sched = open_loop_schedule(tp, seconds, seed, len(tasks))
    reqs = []
    for i in range(len(sched["due"])):
        task = tasks[sched["task"][i]]
        pool = pools[task[1]]
        off = int(sched["offset"][i] % (pool.shape[1] // 2))
        reqs.append(Request(x=pool[:, off:off + int(sched["width"][i])], key=task, id=i))
    return {"reqs": reqs, "due": sched["due"]}


def sweep(ctx, rates, seconds):
    """Open-loop runs at several offered rates on one server (finds the knee)."""
    state = setup(ctx)
    for rate in rates:
        state.update(requests(state, dict(ctx.params, rate_rps=rate), seconds, ctx.seed))
        rec = window(state, seconds, ctx)
        lat = rec.pop("latencies_ms")
        half = len(lat) // 2
        yield {"rate_rps": rate, **{k: v for k, (v, _) in rec["metrics"].items()},
               **rec["info"], "lat_max_ms": float(np.max(lat)),
               "lat_mean_first_half_ms": float(np.mean(lat[:half])),
               "lat_mean_second_half_ms": float(np.mean(lat[half:]))}


def window(state, seconds, ctx):
    srv, reqs, due = state["srv"], state["reqs"], state["due"]
    n = len(reqs)
    sample = set(np.random.default_rng(derived_seed(ctx.seed, 8)).choice(
        n, size=min(CHECK_SAMPLE, n), replace=False).tolist())
    sample.add(int(np.argmax([r.x.shape[1] for r in reqs])))
    keys = [r.key for r in reqs]
    widths = [r.x.shape[1] for r in reqs]
    done_at = np.full(n, np.nan)
    outputs, service, pending = {}, [], []
    nxt, late, loop_gap, t_end, slowest = 0, [], 0.0, None, {"ms": 0.0}
    srv.dispatcher.take_legs()
    t0 = time.perf_counter()
    while nxt < n or pending:
        now = time.perf_counter() - t0
        while nxt < n and due[nxt] <= now:
            pending.append(nxt)
            nxt += 1
        if not pending:
            with ctx.annotate("chipbench.wait"):
                gap = due[nxt] - (time.perf_counter() - t0)
                if gap > 2e-4:
                    time.sleep(gap - 1e-4)
                while time.perf_counter() - t0 < due[nxt]:
                    pass
            continue
        batch = head_of_line(pending, keys, widths, state["max_cols"])
        late.append(now - due[batch[0]])
        with ctx.annotate("chipbench.serve_batch"):
            use0 = resource.getrusage(resource.RUSAGE_THREAD)
            ts = time.perf_counter()
            served = srv.serve([reqs[i] for i in batch])
            te = time.perf_counter()
            use1 = resource.getrusage(resource.RUSAGE_THREAD)
        if t_end is not None:  # the loop's own time between two calls with work due
            loop_gap = max(loop_gap, ts - t_end)
        service.append(te - ts)
        if te - ts > slowest["ms"] / 1e3:
            slowest = _call_usage(ts - t0, te - ts, use0, use1)
        for req, out in served:
            done_at[req.id] = te - t0
            if req.id in sample:
                outputs[req.id] = out
        taken = set(batch)
        pending = [i for i in pending if i not in taken]
        t_end = time.perf_counter() if pending else None
    wall = time.perf_counter() - t0
    legs = np.asarray(srv.dispatcher.take_legs()).reshape(-1, 2)
    lat_ms = (done_at - due) * 1e3
    state["outputs"] = outputs
    failed = int(np.isnan(lat_ms).sum())
    return {
        "metrics": {"serve_p50_ms": (float(np.percentile(lat_ms, 50)), "ms")},
        "attempted": n, "failed": failed, "latencies_ms": lat_ms,
        "record": {"serve_calls": len(service), "service_s": service, "requests": n,
                   "wall_s": wall},
        "info": {"requests": n, "serve_calls": len(service), "window_s": wall,
                 "latency_ms_p99": float(np.percentile(lat_ms, 99)),
                 "completed_rps": n / wall, "offered_rps": n / float(due[-1]),
                 "loop_late_ms_p50": float(np.percentile(late, 50) * 1e3),
                 "service_ms_max": float(np.max(service) * 1e3),
                 "service_ms_p99": float(np.percentile(service, 99) * 1e3),
                 "loop_gap_ms_max": loop_gap * 1e3,
                 "assemble_ms_max": float(legs[:, 0].max(initial=0.0) * 1e3),
                 "dispatch_ms_max": float(legs[:, 1].max(initial=0.0) * 1e3),
                 "slowest_call": slowest,
                 "columns": int(sum(widths))},
    }


def _call_usage(at_s, wall_s, use0, use1) -> dict:
    """Where one call's time went: the thread's CPU time, page faults and
    context switches over it (to tell host work from waiting)."""
    return {"ms": wall_s * 1e3, "at_s": at_s,
            "cpu_ms": 1e3 * (use1.ru_utime + use1.ru_stime - use0.ru_utime - use0.ru_stime),
            "minflt": use1.ru_minflt - use0.ru_minflt, "majflt": use1.ru_majflt - use0.ru_majflt,
            "nvcsw": use1.ru_nvcsw - use0.ru_nvcsw, "nivcsw": use1.ru_nivcsw - use0.ru_nivcsw}


def check(state, record, ctx):
    srv, reqs, outputs = state.pop("srv"), state["reqs"], state.pop("outputs")
    tp = ctx.params
    p = reqs[0].x.shape[0]
    omega = refs.fused_omega(state["fused_seed"], int(tp["n_features"]), p)
    worst = 0.0
    for i, out in outputs.items():
        w_rf = srv.store.get(reqs[i].key).state.w_rf
        ref = refs.transform_columns(w_rf, omega, reqs[i].x)
        worst = max(worst, float(np.abs(out - ref).max() / np.abs(ref).max()))
    nums = {"serve_rel": worst, "unserved": float(record["failed"])}
    record["info"].update(checked=len(outputs), **nums)
    return {name: (nums[name], float(lim)) for name, lim in ctx.cell["limits"].items()}

