"""cellbench: the cell benchmark of magiattention_tpu.

One process runs one cell (a model configuration under one traffic mix) on
the TPU it is started on and prints one JSON result line. Everything a PR
could bend to flatter itself lives here, outside the program: traffic
generation, the FLOP and byte arithmetic, the table of peaks, the reduction
from the device trace to metrics, the plain reference and the comparison
that decides ``correct``. See README.md in this directory.
"""
