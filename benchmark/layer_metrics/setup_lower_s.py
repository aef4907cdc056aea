"""Set-up: seconds of lowering the programs `init` and `train_step` to MLIR
(`compile/lower` records), the Pallas kernels' Mosaic lowering with it.
Nothing to read in a program that makes no compile records."""

from benchmark import compile_reader

read = compile_reader.reader("lower")
