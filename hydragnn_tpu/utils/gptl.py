"""No-op GPTL timing shim + device trace ranges.

The reference ships ``gptl4py_dummy`` (reference:
hydragnn/utils/gptl4py_dummy.py:1-64), a drop-in no-op mirror of the
gptl4py HPC timing library so instrumented code runs unchanged off
Summit. Same pattern here: every gptl4py symbol is a no-op, and the
nvtx-range helper is the program's own span (``obs/spans.py:span``), so
ranges show up in TPU profiler traces when one is active.

    import hydragnn_tpu.utils.gptl as gp
    gp.initialize()
    with gp.nvtx_range("epoch"):
        gp.start("train"); ...; gp.stop("train")
    gp.pr_file("timings.txt"); gp.finalize()
"""

from __future__ import annotations


def initialize() -> int:  # gptl4py_dummy.initialize
    return 0


def finalize() -> int:
    return 0


def start(name: str) -> int:
    return 0


def stop(name: str) -> int:
    return 0


def setoption(*args) -> int:
    return 0


def reset() -> int:
    return 0


def pr(rank: int = 0) -> int:
    return 0


def pr_file(fname: str) -> int:
    return 0


def pr_summary(comm=None) -> int:
    return 0


def pr_summary_file(fname: str, comm=None) -> int:
    return 0


def nvtx_range(name: str):
    """Device trace span (the reference wraps nvtx.range_push/pop): one
    more name for ``obs/spans.py:span``."""
    from hydragnn_tpu.obs.spans import span

    return span(name)


# decorator form, mirroring gptl4py's profile decorator usage
def profile(name=None):
    def wrap(fn):
        label = name or fn.__name__

        def inner(*args, **kwargs):
            with nvtx_range(label):
                return fn(*args, **kwargs)

        return inner

    return wrap
