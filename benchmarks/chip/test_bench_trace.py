"""The trace reduction on a small hand-made trace."""
import pytest

from chip import trace


def _trace():
    ms = 1_000_000  # ns
    host = [("bench/dispatch", 0 * ms, 1 * ms),
            ("bench/batch", 1 * ms, 4 * ms),
            ("bench/dispatch", 4 * ms, 5 * ms),
            ("bench/sync", 5 * ms, 20 * ms)]
    dev0 = [("fusion.1", 0 * ms, 3 * ms),            # overlaps fusion.2
            ("fusion.2", 2 * ms, 6 * ms),
            ("flash_attention_fwd", 8 * ms, 10 * ms),
            ("flash_attention_fwd", 11 * ms, 12 * ms),
            ("all-gather.3", 9 * ms, 15 * ms),       # 9-10, 11-12 hidden
            ("outside", 25 * ms, 30 * ms)]           # after the window
    dev1 = [("while.2", 0 * ms, 20 * ms), ("fusion.1", 0 * ms, 20 * ms)]
    return {"devices": {0: dev0, 1: dev1}, "host": host}


def test_op_name_is_the_instruction_name():
    assert trace.op_name("%fusion.3 = f32[8]{0} fusion(f32[8] "
                         "%flash_attention_fwd.7)") == "fusion.3"
    assert trace.op_name("flash_attention_fwd.7") == "flash_attention_fwd.7"


def test_union_and_subtract():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == 7
    assert trace.subtract([(0, 2), (4, 6)], [(1, 5)]) == 2
    assert trace.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_reduce_busy_kernels_collectives_and_gaps():
    r = trace.reduce(_trace())
    assert r["window_s"] == pytest.approx(0.020)
    d0, d1 = r["devices"][0], r["devices"][1]
    # busy is the union 0-6 and 8-15: 13 ms, not the 17 ms of summed ops
    assert d0["busy_s"] == pytest.approx(0.013)
    assert d1["busy_s"] == pytest.approx(0.020)
    # kernel time summed by name, clipped to the window
    assert d0["ops"]["flash_attention_fwd"] == pytest.approx(0.003)
    assert "outside" not in d0["ops"]
    assert trace.kernel_seconds(r, "flash_attention")[0] == \
        pytest.approx(0.003)
    # the all-gather runs 6 ms, 2 of them under compute: 4 ms exposed
    assert d0["collective_s"] == pytest.approx(0.006)
    assert d0["collective_exposed_s"] == pytest.approx(0.004)
    assert d1["collective_s"] == 0
    # idle: device 0 waits 6-8 while the host is in bench/sync, and 15-20
    assert r["idle_gaps"][0] == ["bench/sync", pytest.approx(0.005)]
    assert ["bench/sync", pytest.approx(0.002)] in r["idle_gaps"]
    assert r["device_ops"][0][0] == "fusion.1"
    assert "while.2" not in dict(r["device_ops"])
    assert trace.seconds_per_step(r, "flash_attention", 2) == \
        pytest.approx(0.003 / 2 / 2)
    assert trace.seconds_per_step(r, "no_such_kernel", 2) is None


def test_control_flow_covers_neither_collectives_nor_idle_time():
    """A collective inside a layer scan's `while` event, with no leaf
    compute over it, is exposed; a stretch of the scan where no leaf op
    runs is idle."""
    ms = 1_000_000
    host = [("bench/dispatch", 0, 1 * ms), ("bench/sync", 1 * ms, 20 * ms)]
    dev = [("while.5", 0, 20 * ms),
           ("fusion.1", 0, 5 * ms),
           ("all-gather.2", 5 * ms, 9 * ms),
           ("fusion.3", 12 * ms, 20 * ms)]
    r = trace.reduce({"devices": {0: dev}, "host": host})
    d0 = r["devices"][0]
    assert d0["busy_s"] == pytest.approx(0.017)
    assert d0["collective_exposed_s"] == pytest.approx(0.004)
    assert r["idle_gaps"] == [["bench/sync", pytest.approx(0.003)]]
    assert d0["ops"]["while.5"] == pytest.approx(0.020)


def test_reduce_refuses_a_trace_without_device_ops():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {0: []}, "host": []})
