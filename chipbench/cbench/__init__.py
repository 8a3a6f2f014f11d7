"""The chip benchmark's own library: the yardstick.

Everything that defines what the benchmark measures lives here, apart
from the program under test: the data generators and failure draws that
make a cell's inputs from its seed, the plain float32 references and the
comparison that decides ``correct``, the table of device peaks, the
FLOP count, and the reduction from a profiler trace to metrics.
"""
