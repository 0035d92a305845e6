"""Backend compiles (persistent-cache hits among them) inside the window of
a closed loop: JAX's compile monitoring event."""


def read(rec):
    if not rec.get("steps"):
        return None
    return rec["compiles_in_window"]
