"""repro.dist — the distribution subsystem.

The paper scales its secure-stream pipelines across workers connected by
encrypted channels (§4-5, Fig. 7/8).  TPU-natively that splits into three
concerns, one module each:

* :mod:`repro.dist.meshctx`            — mesh + logical-axis sharding rules
  (``MeshContext``), the object every model/optimizer/serving layer takes;
* :mod:`repro.dist.collectives`        — secure sharded collectives: the
  ZeroMQ shuffler as an (optionally AEAD-sealed) ``all_to_all``;
* :mod:`repro.dist.pipeline_parallel`  — GPipe-style microbatch schedule
  whose stage boundaries are sealed with the ChaCha20/CW-MAC channel.

``repro.dist.compat`` holds the repo's one ``shard_map`` spelling, and
every mesh is built by :func:`repro.launch.mesh.make_mesh` (Auto axes).
"""
from repro.dist.meshctx import MeshContext, local_mesh_context  # noqa: F401
