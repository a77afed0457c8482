"""Long polls at the head, over real sockets: an empty worker poll is held
until something is queued for its worker, a client's actor_result until
its result is stored; a draining worker and a head shutting down are
never held. Asserted by counts and replies, not by tight timings."""
import contextlib
import functools
import tempfile
import threading
import time

from repro.core.cluster import SyndeoCluster
from repro.core.metrics import SPANS
from repro.core.rendezvous import FileRendezvous
from repro.core.worker import HeadServer, _dec, _enc, _request
from repro.serve.engine import Request, StubEngine
from repro.serve.fleet import serve_fleet

WID = "tcp-lp"


@contextlib.contextmanager
def _head():
    """A live head with one joined worker hosting one actor whose create
    directive has been polled out: the worker's queues are empty."""
    with tempfile.TemporaryDirectory() as d:
        cluster = SyndeoCluster(rendezvous=FileRendezvous(d))
        server = HeadServer(cluster)
        server.attach()
        rpc = functools.partial(_request, "127.0.0.1", server.port,
                                cluster.token)
        try:
            assert rpc({"op": "join", "worker": WID,
                        "resources": {"cpu": 1.0}})["ok"]
            made = rpc({"op": "actor_create", "factory": "f", "actor": "a"})
            assert made["ok"] and made["worker"] == WID
            first = rpc({"op": "poll", "worker": WID})
            assert [d["op"] for d in first["actor_ops"]] == ["actor_create"]
            assert "waited" not in first          # it had work: not held
            yield server, rpc, made["cap"]
        finally:
            server.shutdown()
            cluster.shutdown()


def _in_thread(fn):
    out = {}
    t = threading.Thread(target=lambda: out.update(reply=fn()), daemon=True)
    t.start()
    return t, out


def _poll_wait(call_id):
    """The `head.poll_wait` spans whose reply handed over `call_id`."""
    return [s for s in SPANS.spans() if s.name == "head.poll_wait"
            and call_id in s.attrs["calls"]]


def test_empty_poll_is_woken_by_an_actor_call_from_another_thread():
    with _head() as (server, rpc, cap):
        server.POLL_HOLD_S = 30.0          # only an arrival can end it
        for _ in range(5):                 # until the call lands mid-hold
            t, out = _in_thread(lambda: rpc({"op": "poll", "worker": WID}))
            time.sleep(0.3)
            sent = rpc({"op": "actor_call", "actor": "a", "cap": cap,
                        "payload": _enc({"kind": "x"})})
            assert sent["ok"]
            t.join(timeout=20)
            assert not t.is_alive()
            got = out["reply"]
            assert [d["call"] for d in got["actor_ops"]] == [sent["call"]]
            if "waited" in got:
                break
        assert got["waited"] < 20.0
        (wait,) = _poll_wait(sent["call"])
        assert wait.attrs["woke"] == 1
        assert wait.attrs["calls"] == [sent["call"]]


def test_empty_poll_with_nothing_queued_runs_out():
    with _head() as (server, rpc, _cap):
        t0 = time.perf_counter()
        got = rpc({"op": "poll", "worker": WID})
        assert got["ok"] and got["task"] is None and not got["draining"]
        assert "actor_ops" not in got
        assert 0.0 < got["waited"] < 0.5
        (wait,) = [s for s in SPANS.spans() if s.name == "head.poll_wait"
                   and s.start >= t0]
        assert wait.attrs == {"woke": 0, "calls": []}
        assert wait.end - wait.start < 0.5
        # in-process callers (tests, benchmarks stepping the head) are not held
        assert "waited" not in server.dispatch({"op": "poll", "worker": WID})


def test_held_poll_is_woken_by_a_drain_and_a_draining_poll_is_not_held():
    with _head() as (server, rpc, _cap):
        server.POLL_HOLD_S = 30.0
        t0 = time.perf_counter()
        t, out = _in_thread(lambda: rpc({"op": "poll", "worker": WID}))
        time.sleep(0.3)
        assert rpc({"op": "drain", "worker": WID})["ok"]
        t.join(timeout=20)
        assert not t.is_alive()
        got = out["reply"]
        assert got["draining"]
        # the hosted actor is asked to exit on the draining reply
        assert [d["op"] for d in got["actor_ops"]] == ["actor_exit"]
        woke = [s.attrs["woke"] for s in SPANS.spans()
                if s.name == "head.poll_wait" and s.start >= t0]
        assert woke in ([1], [])          # [] if the drain beat the hold
        for _ in range(3):
            again = rpc({"op": "poll", "worker": WID})
            assert again["draining"] and "waited" not in again


def test_shutdown_wakes_every_held_request():
    with _head() as (server, rpc, cap):
        server.POLL_HOLD_S = server.RESULT_WAIT_CAP_S = 30.0
        sent = rpc({"op": "actor_call", "actor": "a", "cap": cap})
        assert [d["call"] for d in rpc({"op": "poll", "worker": WID})
                ["actor_ops"]] == [sent["call"]]
        held = [_in_thread(lambda: rpc({"op": "poll", "worker": WID})),
                _in_thread(lambda: rpc({"op": "actor_result",
                                        "call": sent["call"]}))]
        time.sleep(0.3)
        server.shutdown()
        for t, _ in held:
            t.join(timeout=10)
            assert not t.is_alive()
        (_, polled), (_, result) = held
        assert polled["reply"]["task"] is None
        assert "actor_ops" not in polled["reply"]
        assert result["reply"] == {"ok": True, "done": False}


def test_actor_result_waits_at_the_head_for_the_worker_report():
    with _head() as (server, rpc, cap):
        server.RESULT_WAIT_CAP_S = 30.0
        sent = rpc({"op": "actor_call", "actor": "a", "cap": cap})
        call = sent["call"]
        # in-process callers get an answer at once
        assert server.dispatch({"op": "actor_result", "call": call}) == \
            {"ok": True, "done": False}
        t, out = _in_thread(lambda: rpc({"op": "actor_result", "call": call}))
        time.sleep(0.3)
        assert rpc({"op": "batch", "worker": WID, "ops": [
            {"op": "actor_result", "worker": WID, "actor": "a",
             "call": call, "value": _enc(42)}]})["replies"][0]["ok"]
        t.join(timeout=20)
        assert not t.is_alive()
        got = out["reply"]
        assert got["done"] and _dec(got["value"]) == 42


def test_each_fleet_call_fetches_its_result_in_one_request(monkeypatch):
    # a cap well past any host stall: one request per result, however slow
    monkeypatch.setattr(HeadServer, "RESULT_WAIT_CAP_S", 8.0)
    t0 = time.perf_counter()
    reqs = [Request(id=i, prompt=[i + 1, 2], max_new_tokens=4)
            for i in range(4)]
    with serve_fleet([StubEngine(batch_slots=2)]) as fleet:
        for r in reqs:
            assert fleet.router.submit(r)
        fleet.router.flush()
    assert all(r.done for r in reqs)
    calls = [s for s in SPANS.spans()
             if s.name == "wire.call" and s.start >= t0]
    assert {s.attrs["kind"] for s in calls} >= {"submit", "tick"}
    assert [s.attrs["polls"] for s in calls] == [1] * len(calls)
    # the worker's polls were held and woken by the calls
    handed = {s.attrs["call"] for s in calls}
    woken = {c for s in SPANS.spans() if s.name == "head.poll_wait"
             and s.start >= t0 and s.attrs["woke"] for c in s.attrs["calls"]}
    assert woken and woken <= handed
