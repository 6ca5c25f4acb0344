"""Output rate limiting (reference: CORE/query/output/ratelimit/* and
TEST/query/ratelimit/*TestCase)."""
import time

from siddhi_tpu import SiddhiManager


def _collect(rt, qname):
    got = []
    rt.add_callback(qname, lambda ts, ins, outs: got.extend(ins or []))
    return got


def test_output_all_every_3_events():
    ql = """
    define stream In (k string, v int);
    @info(name='q')
    from In select k, v output all every 3 events insert into Out;
    """
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(ql)
    got = _collect(rt, "q")
    rt.start()
    h = rt.get_input_handler("In")
    for i in range(7):
        h.send([str(i), i])
    rt.flush()
    # two full windows of 3 flushed; the 7th stays buffered
    assert [e.data[1] for e in got] == [0, 1, 2, 3, 4, 5]
    manager.shutdown()


def test_output_first_every_3_events():
    ql = """
    define stream In (k string, v int);
    @info(name='q')
    from In select k, v output first every 3 events insert into Out;
    """
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(ql)
    got = _collect(rt, "q")
    rt.start()
    h = rt.get_input_handler("In")
    for i in range(7):
        h.send([str(i), i])
    rt.flush()
    assert [e.data[1] for e in got] == [0, 3, 6]
    manager.shutdown()


def test_output_last_every_3_events():
    ql = """
    define stream In (k string, v int);
    @info(name='q')
    from In select k, v output last every 3 events insert into Out;
    """
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(ql)
    got = _collect(rt, "q")
    rt.start()
    h = rt.get_input_handler("In")
    for i in range(7):
        h.send([str(i), i])
    rt.flush()
    assert [e.data[1] for e in got] == [2, 5]
    manager.shutdown()


def test_output_all_every_time():
    ql = """
    define stream In (k string, v int);
    @info(name='q')
    from In select k, v output all every 150 milliseconds insert into Out;
    """
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(ql)
    got = _collect(rt, "q")
    rt.start()
    h = rt.get_input_handler("In")
    for i in range(5):
        h.send([str(i), i])
    deadline = time.time() + 3.0
    while time.time() < deadline and len(got) < 5:
        time.sleep(0.02)
    assert [e.data[1] for e in got] == [0, 1, 2, 3, 4]
    manager.shutdown()


def test_output_snapshot_every_time_grouped():
    ql = """
    define stream In (k string, v int);
    @info(name='q')
    from In select k, sum(v) as total group by k
    output snapshot every 150 milliseconds
    insert into Out;
    """
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(ql)
    batches = []
    rt.add_callback("q", lambda ts, ins, outs: batches.append(ins or []))
    rt.start()
    h = rt.get_input_handler("In")
    h.send(["a", 1])
    h.send(["b", 10])
    h.send(["a", 2])
    # a snapshot may fall between the second and the third send (the
    # first send compiles): wait for the one that has seen all three
    def snaps():
        return [{e.data[0]: e.data[1] for e in b} for b in list(batches)]
    deadline = time.time() + 3.0
    while time.time() < deadline and {"a": 3, "b": 10} not in snaps():
        time.sleep(0.02)
    assert {"a": 3, "b": 10} in snaps()
    manager.shutdown()


def test_output_first_group_by_every_events():
    """FIRST + group-by: each GROUP's first event per window (reference:
    FirstGroupByPerEventOutputRateLimiter)."""
    ql = """
    define stream In (k string, v int);
    @info(name='q')
    from In select k, sum(v) as total group by k
    output first every 4 events insert into Out;
    """
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(ql)
    got = _collect(rt, "q")
    rt.start()
    h = rt.get_input_handler("In")
    h.send(["a", 1])      # first of group a -> emit (a, 1)
    h.send(["b", 10])     # first of group b -> emit (b, 10)
    h.send(["a", 2])      # suppressed
    h.send(["b", 20])     # suppressed; window of 4 complete -> reset
    h.send(["a", 3])      # first of a in new window -> emit (a, 6)
    rt.flush()
    assert [tuple(e.data) for e in got] == [("a", 1), ("b", 10), ("a", 6)]
    manager.shutdown()


def test_output_last_group_by_every_events():
    """LAST + group-by: each group's latest at the window boundary
    (reference: LastGroupByPerEventOutputRateLimiter)."""
    ql = """
    define stream In (k string, v int);
    @info(name='q')
    from In select k, sum(v) as total group by k
    output last every 4 events insert into Out;
    """
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(ql)
    got = _collect(rt, "q")
    rt.start()
    h = rt.get_input_handler("In")
    h.send(["a", 1])
    h.send(["b", 10])
    h.send(["a", 2])      # a's running sum: 3
    h.send(["b", 20])     # window boundary: emit latest per group
    rt.flush()
    assert sorted(tuple(e.data) for e in got) == [("a", 3), ("b", 30)]
    manager.shutdown()


def test_output_last_group_by_every_time():
    """LAST + group-by per-time: latest per group flushed at the tick
    (reference: LastGroupByPerTimeOutputRateLimiter)."""
    ql = """
    define stream In (k string, v int);
    @info(name='q')
    from In select k, sum(v) as total group by k
    output last every 1 sec insert into Out;
    """
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(ql)
    got = _collect(rt, "q")
    rt.start()
    h = rt.get_input_handler("In")
    h.send(["a", 1])
    h.send(["a", 2])
    h.send(["b", 5])
    lim = rt.query_runtimes["q"].rate_limiter
    lim.on_timer(int(time.time() * 1000))
    rt.flush()
    assert sorted(tuple(e.data) for e in got) == [("a", 3), ("b", 5)]
    manager.shutdown()
