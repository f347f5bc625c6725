"""S3-compatible mock object server for the test tree.

The implementation lives in ``thrill_tpu.tools.object_server`` so the
perf sentinel can use the same rig in-process; this module re-exports
it under the test tree's path.
"""

from thrill_tpu.tools.object_server import ObjectServer, main  # noqa: F401
