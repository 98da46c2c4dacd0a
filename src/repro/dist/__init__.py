"""repro.dist — mesh layouts, sharding constraints, and DP collectives.

``repro.dist.sharding`` (imported everywhere as ``shd``) is the single
source of truth for how logical axes (dp / sp / tp) map onto mesh axes in
each execution mode; ``repro.dist.collectives`` carries the cutoff-SGD
bit-array aggregation behind the same layout.
"""
from repro.dist import collectives, sharding  # noqa: F401
