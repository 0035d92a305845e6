"""Mean Krylov iterations per answer (``SolveOutcome.iterations``, a program
counter; BiCGStab(2) counts quarter iterations): read as for the dense cell."""

from chipbench.harness import load_reader

read = load_reader("krylov_iters.dense")
