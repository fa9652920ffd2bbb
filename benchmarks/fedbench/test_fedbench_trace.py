"""The trace reduction on a synthesized trace: busy union, idle share,
heaviest operations and the labels of idle gaps."""
import pytest

from fedbench import traces

MS = 1e6   # ns


def synthetic():
    ops = [("fusion.1", 0 * MS, 40 * MS),       # 0-40
           ("fusion.2", 30 * MS, 20 * MS),      # overlaps: 30-50
           ("conv.3", 60 * MS, 30 * MS),        # 60-90
           ("fusion.1", 95 * MS, 10 * MS),      # 95-105, clipped at 100
           ("conv.3", 200 * MS, 5 * MS)]        # outside the window
    spans = [("fedbench.window", 0, 100 * MS),
             ("Server.run_round", 0, 55 * MS),
             ("Server.evaluate", 55 * MS, 40 * MS)]
    return {"/device:TPU:0": ops}, spans


def test_union_merges_overlaps():
    assert traces.union([(5, 8), (0, 2), (1, 3), (8, 9), (10, 10)]) == \
        [[0, 3], [5, 9]]


def test_reduce_busy_idle_ops_and_gaps():
    devices, spans = synthetic()
    out = traces.reduce(devices, spans,
                        traces.window_of(spans, "fedbench.window"))
    # busy: 0-50, 60-90, 95-100 -> 85 ms of 100
    assert out["window_s"] == pytest.approx(0.1)
    assert out["busy_s"] == pytest.approx(0.085)
    assert out["idle_frac"] == pytest.approx(0.15)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.045)]
    assert [n for n, _ in out["device_ops"]] == ["fusion.1", "conv.3",
                                                 "fusion.2"]
    # gaps: 50-60 (inside run_round until 55, midpoint 55 -> the shorter
    # open span), 90-95 (evaluate)
    assert out["idle_gaps"] == [["Server.evaluate", pytest.approx(0.01)],
                                ["Server.evaluate", pytest.approx(0.005)]]


def test_gap_outside_every_span_and_idle_device():
    ops = {"/device:TPU:0": [("a", 10 * MS, 10 * MS)]}
    spans = [("fedbench.window", 0, 40 * MS)]
    out = traces.reduce(ops, spans, (0, 40 * MS))
    assert out["idle_gaps"][0] == ["fedbench.window", pytest.approx(0.02)]
    assert traces.reduce(ops, [], (0, 40 * MS))["idle_gaps"][0][0] == \
        traces.NO_SPAN
    assert traces.reduce({"/device:TPU:0": []}, spans, (0, 40 * MS)) is None


def test_busy_is_averaged_over_devices():
    ops = {"/device:TPU:0": [("a", 0, 10 * MS)],
           "/device:TPU:1": [("a", 0, 30 * MS)]}
    out = traces.reduce(ops, [], (0, 40 * MS))
    assert out["busy_s"] == pytest.approx(0.02)
    assert out["idle_frac"] == pytest.approx(0.5)


def test_nested_ops_rank_by_their_own_time():
    ops = {"/device:TPU:0": [("while.1", 0, 100 * MS),
                             ("fusion.2", 10 * MS, 60 * MS),
                             ("conv.3", 20 * MS, 10 * MS),
                             ("fusion.4", 80 * MS, 10 * MS)]}
    out = traces.reduce(ops, [], (0, 100 * MS))
    assert out["busy_s"] == pytest.approx(0.1)
    assert out["device_ops"] == [["fusion.2", pytest.approx(0.05)],
                                 ["while.1", pytest.approx(0.03)],
                                 ["conv.3", pytest.approx(0.01)],
                                 ["fusion.4", pytest.approx(0.01)]]


def test_op_name_is_the_instruction_name():
    assert traces.op_name("%fusion.12 = f32[4]{0} fusion(f32[4]{0} %p)") \
        == "fusion.12"
    assert traces.op_name("copy-start.3") == "copy-start.3"
