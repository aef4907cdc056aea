"""Set-up: seconds of Python tracing of the programs `init` and
`train_step` (`compile/trace` records), which the persistent compile cache
never saves. Nothing to read in a program that makes no compile records."""

from benchmark import compile_reader

read = compile_reader.reader("trace")
