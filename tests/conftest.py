"""Suite-wide hypothesis settings.

Property tests jit-compile on their first example, which takes seconds, so
the per-example deadline (200 ms by default) would fail them on compile
time alone.  One profile turns the deadline off for every test.
"""

try:
    from hypothesis import settings
except ModuleNotFoundError:  # tests/helpers.py supplies a fallback runner
    pass
else:
    settings.register_profile("repro", deadline=None)
    settings.load_profile("repro")
