"""Trace reduction and kernel counts, on a synthetic trace."""

import pytest

from bench import roofline
from bench.trace import Event, gaps, self_times, summarize, union_length


def test_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)]
    assert union_length(iv, 0.0, 5.0) == pytest.approx(3.0)
    assert union_length(iv, 1.5, 3.5) == pytest.approx(1.0)
    assert gaps(iv, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert gaps([], 1.0, 2.0) == [(1.0, 2.0)]


def test_self_time_subtracts_nested_ops():
    ev = [Event(0.0, 10.0, "while.1"), Event(1.0, 2.0, "fusion.3"),
          Event(4.0, 3.0, "mutual_reach.1"), Event(12.0, 1.0, "copy.2")]
    own = self_times(ev)
    assert own["while.1"] == pytest.approx(5.0)
    assert own["fusion.3"] == pytest.approx(2.0)
    assert own["copy.2"] == pytest.approx(1.0)


def test_summarize_busy_kernels_and_idle_labels():
    programs = [Event(1.0, 2.0, "jit__offline_pipeline(1)"), Event(6.0, 1.0, "jit_assign(2)")]
    ops = [Event(1.0, 2.0, "%while.121 = (s32[]) while(...)"),
           Event(1.5, 0.5, "%mutual_reach.1 = f32[8192,8192]{1,0} custom-call(...)"),
           Event(6.0, 1.0, "%assign.1 = (s32[1,512]) custom-call(...)")]
    spans = {"bench.poll": [(0.0, 4.0)], "bench.query": [(4.0, 10.0)]}
    s = summarize([(programs, ops)], spans, (0.0, 10.0), {"mutual_reach", "assign"})
    assert s.window_s == pytest.approx(10.0)
    assert s.busy_s == pytest.approx(3.0)
    assert s.kernel_s == {"mutual_reach": pytest.approx(0.5), "assign": pytest.approx(1.0)}
    assert s.kernel_calls == {"mutual_reach": 1, "assign": 1}
    assert s.top_ops[0] == ["while.121", pytest.approx(1.5)]
    # gaps, longest first: (3, 6) mostly under a query, (7, 10), (0, 1)
    assert [[lab, round(x, 6)] for lab, x in s.idle_gaps] == [
        ["bench.query", 3.0], ["bench.query", 3.0], ["bench.poll", 1.0]]


def test_mutual_reach_counts_at_a_small_shape():
    flops, nbytes = roofline.mutual_reach_work(256, 16)
    assert flops == 2 * 256 * 256 * 16
    assert nbytes == 4 * (256 * 256 + 2 * 256 * 16 + 2 * 256)
    least = roofline.least_seconds(flops, nbytes, "TPU v5 lite")
    assert least == pytest.approx(nbytes / 819e9)  # memory-bound at this width
    pct = roofline.roofline_pct([(flops, nbytes)] * 2, 4 * least, "TPU v5 lite")
    assert pct == pytest.approx(50.0)
    assert roofline.roofline_pct([], 1.0, "TPU v5 lite") is None


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
