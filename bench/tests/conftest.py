"""Tests of the benchmark's own code, on the CPU at tiny sizes."""

import os

# committed code alone decides, as in a benchmark run
os.environ.setdefault("REPRO_CALIBRATION", "off")
os.environ.setdefault("REPRO_TUNING", "off")
