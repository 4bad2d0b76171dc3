"""WordCount variant with INJECTABLE pathologies, for the cluster
telemetry acceptance test (test_cluster_multiproc):

* **straggler injection** — a worker process launched with
  ``MRTPU_SKEW_DELAY=<seconds>`` in its environment sleeps that long in
  every map AND reduce body, so every job that worker runs is slow
  (the diagnose CLI must name exactly that worker);
* **key skew injection** — every ``hot*``-prefixed word routes to
  partition 0 while everything else spreads over the remaining
  partitions, so partition P00000's record share is wildly super-uniform
  (the diagnose CLI must name exactly that partition).

Inputs are blobs in the job's storage backend (the zero-shared-
filesystem topology of tests/netwc_mod.py) so worker OS processes need
nothing but the two sockets."""

import os
import time
from typing import Any, Dict, List

_conf: Dict[str, Any] = {"blobs": [], "num_reducers": 4, "storage": None}
RESULT: Dict[str, int] = {}

# Declared NOT associative/commutative/idempotent: the reduce then calls
# reducefn for every key (job.lua:264-284 skips it for a key with one
# value otherwise, and every cold word has one), so a delayed worker's
# reduce of a cold partition is slow too and its median job is a delayed
# one whichever jobs it happens to claim.
associative_reducer = False
commutative_reducer = False
idempotent_reducer = False


def _injected_delay() -> None:
    d = float(os.environ.get("MRTPU_SKEW_DELAY", "0") or 0)
    if d > 0:
        time.sleep(d)


def init(args: Any) -> None:
    if args:
        _conf.update(args)


def taskfn(emit) -> None:
    for i, name in enumerate(_conf["blobs"]):
        emit(i, name)


def mapfn(key: Any, blobname: str, emit) -> None:
    from mapreduce_tpu import storage

    _injected_delay()
    st = storage.router(_conf["storage"])
    for line in st.open_lines(blobname):
        for word in line.split():
            emit(word, 1)


def partitionfn(key: str) -> int:
    from mapreduce_tpu.utils.hashing import fnv1a32

    if key.startswith("hot"):
        return 0  # the injected skew: every hot* key piles onto P00000
    spread = max(_conf["num_reducers"] - 1, 1)
    return 1 + fnv1a32(key.encode("utf-8")) % spread


def reducefn(key: str, values: List[int]) -> int:
    _injected_delay()
    return sum(values)


def finalfn(pairs) -> bool:
    RESULT.clear()
    for key, values in pairs:
        RESULT[key] = values[0]
    return True
