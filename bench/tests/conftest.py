"""CPU tests of the chip benchmark (``python -m pytest bench/tests``).

They run each job's set-up and a short window at a tiny size under
``JAX_PLATFORMS=cpu``, drive whole runs of ``bench/run.py`` past its look
for a chip, and check the trace reduction against a recorded trace."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the runs under test enable JAX's persistent cache; keep it out of the
# checkout's own cache directory
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
    os.environ.get("TMPDIR", "/tmp"), "bench-tests-jax-cache"))
