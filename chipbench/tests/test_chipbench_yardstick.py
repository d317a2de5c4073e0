"""The yardstick's parts: work counts, the peak table, the trace reduction,
the traffic generator, and the plain references against the program."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tinybench  # noqa: E402,F401  (puts the checkout on sys.path)

from chipbench.lib import trace as tr  # noqa: E402
from chipbench.lib.peaks import UnknownDevice, peaks_for  # noqa: E402
from chipbench.lib.traffic import head_of_line, open_loop_schedule, zipf_counts  # noqa: E402
from chipbench.lib.work import least_time, stats_pass_work  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


@pytest.mark.parametrize("n, p, nf, flops, nbytes", [
    # DomainNet real -> clipart at N = 4096: 2.59e12 + 1.036e13 FLOPs
    (154431, 2048, 4096, 2 * 4096 * 2048 * 154431 + 8192**2 * 154431,
     4 * (2048 * 154431 + 8192**2)),
    (3612, 2048, 1024, 2 * 1024 * 2048 * 3612 + 2048**2 * 3612, 4 * (2048 * 3612 + 2048**2)),
    (10, 4, 2, 2 * 2 * 4 * 10 + 16 * 10, 4 * (40 + 16)),
])
def test_stats_pass_work_at_known_shapes(n, p, nf, flops, nbytes):
    assert stats_pass_work(n, p, nf) == (flops, nbytes)


def test_least_time_names_its_bound():
    peaks = peaks_for("TPU v5 lite")
    t, bound = least_time(*stats_pass_work(154431, 2048, 4096), peaks)
    assert bound == "compute" and abs(t - 0.0660) < 5e-4
    t, bound = least_time(1.0, 819e9, peaks)
    assert bound == "memory" and t == pytest.approx(1.0)


def test_peak_table_refuses_an_unknown_device():
    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v9 imaginary")
    with pytest.raises(UnknownDevice):
        peaks_for("cpu")


def test_reduce_events_busy_idle_and_gap_names():
    ms = 1_000_000
    host = [("chipbench.window", 0, 100 * ms), ("chipbench.fit", 0, 60 * ms),
            ("chipbench.wait", 60 * ms, 100 * ms)]
    dev = {"/device:TPU:0": [("gram", 10 * ms, 30 * ms), ("gram", 20 * ms, 40 * ms),
                             ("eigh_copy", 70 * ms, 80 * ms), ("late", 95 * ms, 120 * ms)]}
    red = tr.reduce_events(host, dev)
    assert red["window_ns"] == 100 * ms
    assert red["busy_ns"] == (30 + 10 + 5) * ms  # union, clipped to the window
    idle = dict(red["idle_gaps"])
    assert idle["chipbench.fit"] == (10 + 20) * ms  # gaps split at the span edge (60 ms)
    assert idle["chipbench.wait"] == (10 + 15) * ms
    ops = dict(red["device_ops"])
    assert ops["gram"] == 40 * ms and ops["late"] == 5 * ms
    bd = tr.breakdown(red)
    assert bd["device_ops"][0] == ["gram", 0.04]


def test_reduce_xplane_reads_a_recorded_trace():
    """A trace recorded on the CPU (annotations only, no device plane): the
    reader finds the window and the harness's spans, and no busy time."""
    red = tr.reduce_xplane(str(DATA / "cpu_window.xplane.pb"))
    assert red["window_ns"] > 0
    assert len(red["spans"]["chipbench.fit"]) == 3
    assert red["busy_ns"] == 0
    assert red["idle_gaps"][0][0] in ("chipbench.fit", "outside chipbench spans")


def test_union_and_gaps():
    assert tr.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert tr.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_schedule_offers_the_same_work_for_every_seed():
    tp = {"rate_rps": 500, "width_lo": 1, "width_hi": 256, "zipf_s": 1.0}
    a = open_loop_schedule(tp, 4.0, 1, 6)
    b = open_loop_schedule(tp, 4.0, 2**31 + 99, 6)
    assert len(a["due"]) == len(b["due"]) == 2000
    assert np.allclose(np.sort(np.diff(a["due"], prepend=0)), np.sort(np.diff(b["due"], prepend=0)))
    assert sorted(a["width"]) == sorted(b["width"]) and a["width"].min() >= 1
    assert a["width"].max() <= 256
    assert np.bincount(a["task"]).tolist() == np.bincount(b["task"]).tolist()
    assert not np.array_equal(a["width"], b["width"])
    assert a["due"][-1] == pytest.approx(4.0, rel=0.05)
    assert zipf_counts(10, 3, 1.0).tolist() == [6, 3, 1][:3] or sum(zipf_counts(10, 3, 1.0)) == 10


def test_head_of_line_batches_the_head_task_up_to_the_widest_bucket():
    keys = ["a", "b", "a", "a", "a"]
    widths = [100, 5, 100, 50, 10]
    assert head_of_line([0, 1, 2, 3, 4], keys, widths, 256) == [0, 2, 3]
    assert head_of_line([1, 2], keys, widths, 256) == [1]
