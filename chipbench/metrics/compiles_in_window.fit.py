"""Programs XLA compiled or loaded inside the window (want 0): the
``jit.compile`` events of the program's tracer.  Nothing to read where
the program's spans carry no ``args.id``: such a tracer records no
compilations."""


def read(run):
    xs = [e for e in run.spans if e.get("ph") == "X"]
    if not any("id" in e.get("args", {}) for e in xs):
        return None
    return sum(e["name"] == "jit.compile" for e in xs)
