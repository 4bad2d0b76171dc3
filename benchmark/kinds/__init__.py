"""One module per kind of configuration; ``run.py`` imports
``benchmark.kinds.<kind>`` by the configuration file's ``kind``."""


def counter(name: str, **labels) -> float:
    """A counter of the program's metrics registry, summed over the
    label sets that match."""
    from mapreduce_tpu.obs.metrics import REGISTRY

    return REGISTRY.sum(name, **labels)


def kernel_faults(kernels, mode: str):
    """Every kernel in *kernels* was built in *mode* (``mosaic`` on the
    TPU, ``interpret`` elsewhere) and none in the other; yields what is
    not so."""
    other = "interpret" if mode == "mosaic" else "mosaic"
    for kernel in kernels:
        if not counter("mrtpu_pallas_kernel_builds_total",
                       kernel=kernel, mode=mode):
            yield f"kernel {kernel!r} was never built in {mode} mode"
        if counter("mrtpu_pallas_kernel_builds_total",
                   kernel=kernel, mode=other):
            yield f"kernel {kernel!r} was built in {other} mode"
