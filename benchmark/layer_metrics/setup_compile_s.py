"""Set-up: seconds of the backend stage of the programs `init` and
`train_step` (`compile/backend` records): XLA's compile on a persistent-
cache miss, the fetch and load onto the chip on a hit. Nothing to read in a
program that makes no compile records."""

from benchmark import compile_reader

read = compile_reader.reader("backend")
