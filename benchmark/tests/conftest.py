"""The benchmark's tests run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""
import os
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ["SIDDHI_TPU_STATE_DIR"] = tempfile.mkdtemp(
    prefix="siddhi_bench_test_state_")
