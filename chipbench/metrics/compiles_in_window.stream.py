"""compiles_in_window.stream: executables JAX produced (compiled or
loaded from the persistent cache) while the window was open."""


def read(rec):
    return rec["compiles_in_window"] if rec["mode"] == "stream" else None
