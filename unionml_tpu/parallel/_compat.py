"""The one import point for ``shard_map``.

Every per-device program in this package routes through ``jax.shard_map``
(replication check spelled ``check_vma``) via this module.
"""

import jax

shard_map = jax.shard_map
