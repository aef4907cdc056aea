"""Set-up: persistent-cache misses of the programs `init` and `train_step`,
the sum of the `count` of their `compile/backend` records (1 on a miss, 0
on a hit). On a warm run anything above 0 is a cache key that moved.
Nothing to read in a program that makes no compile records or keeps no
cache."""

from benchmark import compile_reader

read = compile_reader.reader("backend", misses=True)
