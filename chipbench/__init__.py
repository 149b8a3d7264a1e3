"""chipbench: the served-path benchmark of gigapaxos_tpu on the chip.

The yardstick lives here, where PRs that change the program cannot change it:
traffic generation, the plain reference, the reduction from traces and phase
clocks to metrics, the table of peaks and the byte counts of the kernels.
From the program it takes only the system under test (``InProcessCluster``
and the client), its phase-clock histograms and the kernel names the trace
prints.  See ``README.md`` beside this file.
"""
