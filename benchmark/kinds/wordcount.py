"""Kind ``wordcount``: whole-corpus jobs through
``DeviceWordCount.count_bytes``, one after another.

A unit is one job: host ``bytes`` in, host ``dict`` out — split, upload,
waves, readback and materialize all inside its clock.  The answer is
compared with the reference between jobs: outside the job's clock,
inside the window that ``wc_rate`` divides by.
"""

from __future__ import annotations

import importlib
import statistics
import time

import jax

from benchmark import corpus
from benchmark.kinds import counter, kernel_faults

def _resolve(path: str):
    module, attr = path.rsplit(".", 1)
    return getattr(importlib.import_module(module), attr)


class Cell:
    #: host spans this kind writes into a trace; run.py labels idle gaps
    #: by them and takes the hull of WINDOW_SPAN as the traced window
    SPANS = ("wc.job", "wc.check")
    WINDOW_SPAN = "wc.job"

    def __init__(self, config: dict, cell: dict, seed: int, devices) -> None:
        from mapreduce_tpu.engine import DeviceWordCount
        from mapreduce_tpu.parallel import make_mesh

        program = config["program"]
        self.kernels = list(program["kernels"])
        self.mode = "mosaic" if devices[0].platform == "tpu" else "interpret"
        t0 = time.monotonic()
        self.text, self.reference = corpus.make_corpus(config["corpus"], seed)
        self.t_corpus = time.monotonic() - t0
        # the program's served default, called as a user calls it
        self.wc = DeviceWordCount(
            make_mesh(devices=devices), chunk_len=int(program["chunk_len"]),
            config=_resolve(program["engine_config"])())

    def warm(self, units: int) -> None:
        """Whole jobs on the real corpus: compiles (or fetches) the wave
        program and primes the readback and slice programs."""
        for _ in range(units):
            if not self.unit()["ok"]:
                raise RuntimeError("warm-up job differs from the reference")

    def unit(self) -> dict:
        tm: dict = {}
        d0 = counter("mrtpu_device_dispatches_total", program="wave")
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("wc.job"):
            counts = self.wc.count_bytes(self.text, timings=tm)
        seconds = time.monotonic() - t0
        with jax.profiler.TraceAnnotation("wc.check"):
            dispatched = counter("mrtpu_device_dispatches_total",
                                  program="wave") - d0
            # one dispatch per wave, also when a capacity retry ran more
            ok = (counts == self.reference
                  and (tm["retries"] > 0 or dispatched == tm["waves"]))
        return {"ok": ok, "seconds": seconds, "work": len(self.text),
                "timings": tm}

    def faults(self):
        """Conditions of ``correct`` that hold for the run as a whole."""
        return kernel_faults(self.kernels, self.mode)

    def derived(self, values: dict, n_chips: int, device_kind: str) -> dict:
        return {}


def end_to_end(records: list, window_s: float) -> dict:
    """``wc_job_s``: the median job; ``wc_rate``: corpus bytes of all
    completed jobs over the window's seconds, in MB/s."""
    done = [r for r in records if r["ok"]]
    if not done:
        return {}
    return {"wc_job_s": statistics.median(r["seconds"] for r in done),
            "wc_rate": sum(r["work"] for r in done) / window_s / 1e6}
