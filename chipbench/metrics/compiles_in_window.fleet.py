"""Backend compiles inside the window (JAX's compile monitoring event), read
as for the dense cell."""

from chipbench.harness import load_reader

read = load_reader("compiles_in_window.dense")
