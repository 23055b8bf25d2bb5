"""Seconds of the program's set-up outside the data: the flight record's
``setup`` event, its top-level phases (``setup.backend``, ``model_init``,
``restore``, ``step_builders``, ``introspect``, ``tensorboard``,
``drift_reference``, ``graftcheck``, ``exec_cache``, ``manifest``) other
than ``setup.data``."""

META = {"layer": "entry (api.py)", "unit": "s", "better": "lower", "source": "program_span", "moves": "setup_s"}


def read(ctx):
    import program_spans

    phases = program_spans.setup_phases(ctx["flight"])
    if not phases:
        return None
    return sum(float(p["s"]) for name, p in phases.items() if p.get("parent") not in phases and name != "setup.data")
