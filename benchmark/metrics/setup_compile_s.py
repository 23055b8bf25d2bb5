"""Sum of JAX's ``backend_compile_duration`` events before the window
opens: compilation, or the read from the persistent cache in its place."""

META = {"layer": "entry (api.py)", "unit": "s", "better": "lower", "source": "program_span", "moves": "setup_s"}


def read(ctx):
    return ctx["setup"]["compile_s"]
