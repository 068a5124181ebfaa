import os

# the benchmark's tests run on the CPU; only bench/run.py needs the chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")
