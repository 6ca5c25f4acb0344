"""Explain/introspection smoke test: boot a sample app behind the REST
service, push traffic, then assert the full introspection surface works —
`GET /explain` returns an operator tree with XLA cost analysis,
`GET /healthz` distinguishes readiness from liveness, `GET /trace/<query>`
serves DETAIL batch traces in the runtime's span names, and the
`siddhi_state_bytes` family scrapes.  Run via `make explain-smoke` (CI/tooling hook of the
observability v2 layer; see README "Observability")."""
import json
import re
import sys
import urllib.error
import urllib.request

sys.path.insert(0, ".")

from siddhi_tpu.service import SiddhiRestService  # noqa: E402

APP = """@app:name('ExplainApp')
@app:statistics('DETAIL')
define stream Trades (symbol string, price double, volume long);
@info(name='vwap')
from Trades#window.lengthBatch(16)
select symbol, sum(price * volume) / sum(volume) as vwap
group by symbol insert into Vwap;
@info(name='spike')
from every e1=Trades[volume > 10] -> e2=Trades[price > e1.price]
select e1.symbol as symbol, e1.price as p1, e2.price as p2
insert into Spikes;
"""


def _get(base, path):
    return urllib.request.urlopen(f"{base}{path}")


def main() -> int:
    svc = SiddhiRestService().start()
    try:
        base = f"http://127.0.0.1:{svc.port}"
        req = urllib.request.Request(f"{base}/siddhi-apps",
                                     data=APP.encode(), method="POST")
        assert urllib.request.urlopen(req).status == 201, "deploy failed"
        events = [["ACME", 50.0 + i, 10 + i] for i in range(64)]
        body = json.dumps({"events": events}).encode()
        urllib.request.urlopen(urllib.request.Request(
            f"{base}/siddhi-apps/ExplainApp/streams/Trades", data=body,
            method="POST"))
        svc.manager.runtimes["ExplainApp"].flush()

        # 1. EXPLAIN: operator tree + per-step cost analysis
        for qname, kind in (("vwap", "plain"), ("spike", "pattern")):
            rep = json.loads(_get(
                base, f"/siddhi-apps/ExplainApp/explain/{qname}")
                .read().decode())
            assert rep["kind"] == kind, rep["kind"]
            avail = [c for c in rep["steps"].values()
                     if c.get("available")]
            assert avail, f"{qname}: no analyzable step"
            c = avail[0]
            assert c["bytes_accessed"] > 0 and \
                c["memory"]["peak_bytes"] > 0, c
            assert rep["state"]["total_bytes"] > 0
            assert "eligible" in rep["fusion"]

        # 2. /healthz: live + ready, per-stream staleness/backlog
        hz = json.loads(_get(base, "/healthz").read().decode())
        assert hz["live"] is True and hz["ready"] is True, hz
        strm = hz["apps"]["ExplainApp"]["streams"]["Trades"]
        assert strm["backlog"] == 0 and strm["status"] == "ok", strm
        assert _get(base, "/healthz/ready").status == 200
        assert _get(base, "/healthz/live").status == 200

        # 3. /trace/<query>: DETAIL batch traces, spans named by the
        # runtime's span taxonomy (observability/phases.py)
        evs = json.loads(_get(base, "/trace/vwap").read().decode())["traces"]
        assert evs, "no DETAIL traces"
        stages = {s["stage"] for t in evs for s in t["spans"]}
        assert {"stage", "h2d", "dispatch"} <= stages, stages

        # 4. /metrics: the state-bytes family scrapes with components
        text = _get(base, "/metrics").read().decode()
        assert "# TYPE siddhi_state_bytes gauge" in text
        m = re.search(r'siddhi_state_bytes\{app="ExplainApp",'
                      r'query="vwap",component="window"\} (\d+)', text)
        assert m and int(m.group(1)) > 0, "state bytes gauge missing"

        print(f"explain-smoke OK: {len(evs)} DETAIL traces, "
              f"vwap window state {m.group(1)} bytes, "
              f"healthz live+ready")
        return 0
    finally:
        svc.stop()


if __name__ == "__main__":
    sys.exit(main())
