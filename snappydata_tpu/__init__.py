"""snappydata_tpu — a TPU-native distributed in-memory analytics database.

A from-scratch JAX/XLA re-design of the capabilities of SnappyData
(reference: SnappyDataInc/snappydata @ /root/reference): a mutable column +
row store fused with a SQL engine whose hot path (scan / filter / project /
hash-aggregate / hash-join) executes as jitted XLA programs on TPU, with
plan caching keyed on literal-tokenized SQL, partitioned/replicated/
collocated tables over a `jax.sharding.Mesh`, snapshot-isolation mutation
via versioned batch manifests, exactly-once streaming ingest, and AQP
(stratified samples / TopK) as a plug-in layer.

Layer map (mirrors reference SURVEY.md §1):
  storage/   — column-batch format, encodings, deltas   (ref: encoders/)
  sql/       — lexer/parser/analyzer, logical plans     (ref: SnappyParser)
  engine/    — jitted physical operators + plan cache   (ref: codegen exec)
  parallel/  — murmur3 partitioner, bucket map, mesh    (ref: StoreHashFunction)
  catalog/   — table metadata + persistence             (ref: SnappySessionCatalog)
  cluster/   — locator/lead/server runtime              (ref: cluster/)
  streaming/ — exactly-once sink                        (ref: SnappySinkCallback)
  aqp/       — sampling, CMS/TopK                       (ref: SnappyContextFunctions)
"""

__version__ = "0.1.0"

import os as _os

import jax as _jax

# LONG/TIMESTAMP columns are int64; without x64, jnp.asarray silently wraps
# them to int32. Float width stays policy-controlled (config.use_float64):
# decimals are explicitly cast to float32 on TPU in types.device_dtype.
_jax.config.update("jax_enable_x64", True)

# Persistent compile cache. JAX reads JAX_COMPILATION_CACHE_DIR itself, so
# when it is set nothing is set here; otherwise the cache sits at a fixed
# place in the checkout (the path is part of the cache key: a directory
# that moves never hits).
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update("jax_compilation_cache_dir", _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache"))

from snappydata_tpu.session import SnappySession  # noqa: E402,F401
from snappydata_tpu import config  # noqa: E402,F401
