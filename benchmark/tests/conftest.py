"""The benchmark's own tests (not part of the repository's tier-1 suite):

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

They run on the CPU at tiny sizes, or compile for a described chip; none
prints or asserts a device metric."""
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))
