"""The benchmark's yardstick: discovery of cells by name, traffic
generation, the plain reference, the serve loops, the trace reduction
and the comparison that decides ``correct``. Nothing here imports the
system under test except ``system.py``, which builds and drives it."""
