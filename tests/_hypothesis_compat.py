"""Shared hypothesis entry point for the property-based tests.

Import ``given``, ``settings``, ``st`` from here instead of from
hypothesis directly. Loading this module installs the suite's settings
profile: no per-example deadline, because the first example of a
property test pays JAX's trace-and-compile time, which has nothing to do
with the property under test.
"""
from hypothesis import given, settings, strategies as st

settings.register_profile("repro", deadline=None)
settings.load_profile("repro")

__all__ = ["given", "settings", "st"]
