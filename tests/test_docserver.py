"""DocServer / HttpDocStore specifics beyond the shared backend suite in
test_coord.py (which runs the full docstore + task fault tests over the
"http" param): retry exactly-once semantics, error mapping, durable
restart."""

import http.client
import json

import pytest

from mapreduce_tpu.coord.docserver import DocServer, HttpDocStore
from mapreduce_tpu.coord.docstore import DirDocStore


@pytest.fixture
def srv():
    s = DocServer().start_background()
    yield s
    s.shutdown()


def _post(srv, payload):
    cnn = http.client.HTTPConnection(srv.host, srv.port, timeout=10)
    cnn.request("POST", "/rpc", body=json.dumps(payload).encode())
    r = cnn.getresponse()
    body = json.loads(r.read())
    cnn.close()
    return body


def test_retried_mutation_applies_once(srv):
    """The same request id replayed (a client reconnect after a broken
    socket) must not double-apply: the recorded response comes back and
    state is unchanged."""
    ins = {"op": "insert", "coll": "c", "doc": {"_id": "a", "n": 0},
           "rid": "rid-ins"}
    assert _post(srv, ins)["ok"]
    assert _post(srv, ins)["ok"]  # replayed, not re-inserted
    assert srv.store.count("c") == 1

    inc = {"op": "update", "coll": "c", "query": {"_id": "a"},
           "update": {"$inc": {"n": 1}}, "rid": "rid-inc"}
    assert _post(srv, inc)["result"] == 1
    assert _post(srv, inc)["result"] == 1  # replay: same answer, no 2nd $inc
    assert srv.store.find_one("c", {"_id": "a"})["n"] == 1

    claim = {"op": "find_and_modify", "coll": "c", "query": {"n": 1},
             "update": {"$set": {"who": "w1"}}, "rid": "rid-claim"}
    first = _post(srv, claim)["result"]
    again = _post(srv, claim)["result"]
    assert first == again  # a retried claim cannot double-claim


def test_concurrent_retry_waits_for_inflight_original():
    """A retry arriving while the original is STILL executing must wait for
    the recorded response, not re-apply (the in-flight reservation)."""
    import threading
    import time

    from mapreduce_tpu.coord.docstore import MemoryDocStore

    class SlowStore(MemoryDocStore):
        def update(self, *a, **kw):
            time.sleep(0.4)
            return super().update(*a, **kw)

    srv = DocServer(SlowStore()).start_background()
    try:
        srv.store.insert("c", {"_id": "a", "n": 0})
        req = {"op": "update", "coll": "c", "query": {"_id": "a"},
               "update": {"$inc": {"n": 1}}, "rid": "rid-race"}
        replies = []

        def fire():
            replies.append(_post(srv, req))

        t1 = threading.Thread(target=fire)
        t2 = threading.Thread(target=fire)
        t1.start()
        time.sleep(0.1)  # original is mid-update when the duplicate lands
        t2.start()
        t1.join(timeout=30)
        t2.join(timeout=30)
        assert not (t1.is_alive() or t2.is_alive())
        assert [r["ok"] for r in replies] == [True, True]
        assert srv.store.find_one("c", {"_id": "a"})["n"] == 1  # applied once
    finally:
        srv.shutdown()


def test_eviction_straggler_fails_loudly_not_double_applied(srv):
    """Drive more than _DEDUPE_CAP mutating RPCs, then replay the very
    first rid: its recorded answer is long evicted, so the server must
    refuse (DedupeEvictedError) — NOT silently re-apply the $inc.  A rid
    still inside the cap keeps the normal replay contract."""
    import http.client as hc
    import json as j

    from mapreduce_tpu.coord.docserver import _DEDUPE_CAP

    cnn = hc.HTTPConnection(srv.host, srv.port, timeout=30)

    def rpc(payload):
        cnn.request("POST", "/rpc", body=j.dumps(payload).encode())
        r = cnn.getresponse()
        return j.loads(r.read())

    srv.store.insert("c", {"_id": "a", "n": 0})
    first = {"op": "update", "coll": "c", "query": {"_id": "a"},
             "update": {"$inc": {"n": 1}}, "rid": "sess:1"}
    assert rpc(first)["result"] == 1
    # flood the cache past its cap with other mutations from the session
    for i in range(2, _DEDUPE_CAP + 10):
        assert rpc({"op": "update", "coll": "c", "query": {"_id": "a"},
                    "update": {"$set": {"x": i}},
                    "rid": f"sess:{i}"})["ok"]
    # a straggling retry of the evicted first rid: loud refusal...
    reply = rpc(first)
    assert reply["ok"] is False
    assert reply["type"] == "DedupeEvictedError"
    # ...and crucially NOT a silent second $inc
    assert srv.store.find_one("c", {"_id": "a"})["n"] == 1
    # a rid still inside the cap replays normally (recorded answer back)
    last = _DEDUPE_CAP + 9
    replayed = rpc({"op": "update", "coll": "c", "query": {"_id": "a"},
                    "update": {"$set": {"x": last}},
                    "rid": f"sess:{last}"})
    assert replayed["ok"]
    cnn.close()


def test_legacy_opaque_rids_keep_old_semantics(srv):
    """Pre-SESSION:SEQ clients (opaque uuid rids) can't be watermarked;
    they keep the within-cap replay contract and are never refused."""
    ins = {"op": "insert", "coll": "c2", "doc": {"_id": "z"},
           "rid": "deadbeef"}  # no colon: legacy form
    assert _post(srv, ins)["ok"]
    assert _post(srv, ins)["ok"]  # replayed
    assert srv.store.count("c2") == 1


def test_reads_are_not_deduped(srv):
    srv.store.insert("c", {"_id": "a"})
    find = {"op": "find", "coll": "c", "rid": "rid-find"}
    assert len(_post(srv, find)["result"]) == 1
    srv.store.insert("c", {"_id": "b"})
    assert len(_post(srv, find)["result"]) == 2  # fresh execution


def test_error_mapping(srv):
    store = HttpDocStore(f"{srv.host}:{srv.port}")
    srv.store.insert("c", {"_id": "a", "x": 1})
    with pytest.raises(ValueError):
        store.find("c", {"x": {"$regex": "unsupported"}})
    with pytest.raises(NotImplementedError):
        store.find_and_modify("c", {}, {"$set": {"x": 1}},
                              sort_key=lambda d: d["x"])
    assert store.ping()
    store.close()


def test_durable_board_survives_restart(tmp_path):
    """--root mode: the board state is a DirDocStore, so a docserver
    restart (the mongod-restart story) loses nothing."""
    root = str(tmp_path / "board")
    s1 = DocServer(DirDocStore(root)).start_background()
    c1 = HttpDocStore(f"{s1.host}:{s1.port}")
    c1.insert("jobs", {"_id": "j1", "status": 0})
    c1.close()
    s1.shutdown()

    s2 = DocServer(DirDocStore(root)).start_background()
    c2 = HttpDocStore(f"{s2.host}:{s2.port}")
    assert c2.find_one("jobs", {"_id": "j1"})["status"] == 0
    c2.close()
    s2.shutdown()
