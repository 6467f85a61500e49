"""What depends on the lane a configuration serves, one pair of files a lane.

A configuration file names its lane under ``"lane"``; without the key it is
``rows``.  ``bench/harness.py`` loads ``bench/lanes/<lane>.py`` by path
from the checkout's root, and ``bench/loadgen.py`` loads
``bench/lanes/<lane>_load.py`` (which imports no JAX) beside itself, so a
lane is added as two new files.  ``rows.py`` and ``rows_load.py`` say what
each side gives.
"""
