"""Launchers: production mesh, dry-run, train, serve.

NOTE: importing ``dryrun`` sets XLA_FLAGS for 512 placeholder devices — only
do that in dedicated dry-run processes, never from tests or benchmarks.
"""
from .mesh import make_production_mesh, make_elastic_mesh  # noqa: F401
