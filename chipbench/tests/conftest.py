"""The harness's own tests: on the CPU, at sizes a test run holds.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
