"""The benchmark's tests run on the CPU backend."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
