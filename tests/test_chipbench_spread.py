"""The benchmark's own tests under tier-1 (ISSUE 25 asked for this file; PERF.md
section 7, ROADMAP queue 3 item 8): every case of
``chipbench/tests/test_chipbench.py`` is collected here as a case of its own,
and the file under ``chipbench/`` stays as it is. They need no chip; the cases
that hold the reference against the program (``chipbench/tests/parity_cases.py``)
run JAX on the CPU."""

from chipbench.tests.test_chipbench import *  # noqa: F401,F403
