"""setup_s: from the moment JAX has found the chips to the start of the
measured window: the program's imports, data, building and warming the
server, compiling or loading every launch shape, and an open loop's warm
traffic. The process's own start (Python, importing JAX, the TPU
runtime's start) comes before it and is reported apart, under
``info.runtime_start_s``: no change to the program moves it, and it
swings by seconds from run to run."""


def read(run):
    return run.setup_s
