"""Compiled-executable (de)serialization for the persistent tier.

The persistent tier (``MPI4JAX_TPU_COMPILE_CACHE_DIR``) stores
*loaded-executable* artifacts: the XLA executable bytes plus the call
signature trees, via ``jax.experimental.serialize_executable`` (the same
machinery JAX's own persistent compilation cache rides).

The tier is opt-in, so a failure here is reported, not hidden: an
operator who named a cache directory and gets nothing stored — or
recompiles on every boot — must see why.  ``dumps`` and ``loads`` raise
whatever the serializer raises (an unserializable program, a backend
whose PjRt client cannot serialize, a payload this process cannot
reconstruct).  diskcache's container digest has already filtered
bit-rot, and the key carries the toolchain versions, so a payload that
reaches ``loads`` was written by this same toolchain.

Payload format (inside the diskcache container): pickle of
``(SERIALIZED_EXECUTABLE_BYTES, in_tree, out_tree)``.  Only payloads
this program wrote are ever unpickled.
"""

from __future__ import annotations

import pickle

_PROTO = 4  # stable across the supported Pythons


def dumps(compiled) -> bytes:
    """Serialize a ``jax.stages.Compiled`` into an artifact payload."""
    from jax.experimental import serialize_executable

    payload, in_tree, out_tree = serialize_executable.serialize(compiled)
    return pickle.dumps((payload, in_tree, out_tree), protocol=_PROTO)


def loads(data: bytes):
    """Deserialize an artifact payload back into a callable
    ``jax.stages.Compiled``."""
    from jax.experimental import serialize_executable

    payload, in_tree, out_tree = pickle.loads(data)
    return serialize_executable.deserialize_and_load(
        payload, in_tree, out_tree)
