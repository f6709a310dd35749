"""Test-session bootstrap: one hypothesis profile for the property tests.

Their first example pays for JIT compilation, which takes far longer than
hypothesis's per-example deadline, so the deadline is off.  Examples are
derandomized and no example database is written, so every run (and every
xdist worker) draws the same cases.
"""

from hypothesis import settings

settings.register_profile("repro", deadline=None, derandomize=True,
                          database=None)
settings.load_profile("repro")
