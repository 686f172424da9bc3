"""The benchmark's harness: data, traffic, the closed-loop client, the
comparison that decides ``correct``, and the reduction of a profiler trace.

Nothing here is imported by the engine.  The engine is imported only where
it is driven: :mod:`harness.client` (``QueryServer``) and
:mod:`harness.runner` (its compilation cache).
"""
