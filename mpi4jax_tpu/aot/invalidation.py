"""Pinned-program staleness: capture the world once, refuse it moved.

A :class:`~.pinning.PinnedProgram` deliberately does NONE of the per-call
work the dispatch fast path still pays — no flag parsing, no cache-key
hashing, no program-cache lookup.  The price of that bargain is that a
pinned executable can silently serve **old-world code**: a config flag
flipped after the pin (different algorithm, different resilience plan,
different telemetry bracketing) or an elastic epoch advance (the world
shrank/grew; the program's mesh and group tables address dead ranks)
would execute without anyone noticing — exactly the failure mode the
program-cache key folding exists to prevent.

So pinning reuses the same revocation machinery, inverted: instead of
folding the world into a key that is REBUILT per call, a
:class:`WorldStamp` captures the world ONCE at pin time —

- the configuration stamp (the ``utils/config.config_stamp`` shape): the
  programmatic-override epoch plus the raw (unparsed) environment
  fingerprint of every declared flag EXCEPT the storage-only
  compile-cache knobs — retuning where artifacts are stored must not
  revoke live programs;
- the elastic communication epoch (``resilience/elastic.current_epoch``)
  — every ``advance_epoch`` also bumps the config epoch, but the epoch
  is kept separately so the error can say *which* world moved;

and validation is two comparisons: an int (almost always unequal on any
programmatic change, checked first) and a tuple of raw strings.  No
parsing, no hashing, no dict lookups beyond the ``os.environ`` reads the
fingerprint itself is made of.

A failed check raises :class:`StaleProgramError` tagged ``MPX129``
(``mpx.analyze`` converts the raise into a finding; the message names
the stale half and the re-pin recipe).  Staleness follows the WORLD,
not the program: restoring the exact captured configuration (flip a
flag and flip it back) legitimately revalidates the stamp — same stamp,
same trace.  An epoch advance, by contrast, is permanent (epochs are
monotonic): only a re-pin (``PinnedProgram.repin`` / ``mpx.compile``)
re-enters the new world.

Pure Python (no jax): the whole module runs under the isolated test
loader (tests/test_aot_pure.py).
"""

from __future__ import annotations

import os
from typing import Optional

from ..utils import config
from ..analysis.report import mpx_error

# Flags that only decide where compiled artifacts are STORED — they never
# shape a trace, so retuning them must not revoke live pinned programs
# (a long-running server enabling the cache dir for future pins would
# otherwise stale its serving step for nothing).
STORAGE_ONLY_FLAGS = (
    "MPI4JAX_TPU_COMPILE_CACHE_DIR",
    "MPI4JAX_TPU_COMPILE_CACHE_MAX_BYTES",
)

_WORLD_FLAG_NAMES = tuple(
    n for n in config.FLAG_NAMES if n not in STORAGE_ONLY_FLAGS
)


def _world_stamp_value() -> tuple:
    """The trace-shaping configuration stamp: the programmatic epoch plus
    the raw environment fingerprint of every declared flag EXCEPT the
    storage-only ones (mirrors ``config.config_stamp`` otherwise)."""
    return (config.config_epoch(),
            tuple(map(os.environ.get, _WORLD_FLAG_NAMES)))


class StaleProgramError(RuntimeError):
    """A pinned program was called after the world it was compiled for
    was revoked (configuration stamp or elastic epoch change).  Carries
    ``mpx_code == "MPX129"``; re-pin with ``program.repin()`` or a fresh
    ``mpx.compile`` (``mpx.elastic.run`` does this automatically for
    step functions that expose ``repin``)."""


def _current_epoch() -> int:
    # lazy: the resilience package is optional under isolated loaders,
    # and a world that never imported it is at epoch 0 by definition
    try:
        from ..resilience.elastic import current_epoch
    except ImportError:
        return 0
    return current_epoch()


class WorldStamp:
    """One captured (config stamp, elastic epoch) pair + the check."""

    __slots__ = ("stamp", "epoch")

    def __init__(self, stamp, epoch: int):
        self.stamp = stamp
        self.epoch = epoch

    @classmethod
    def capture(cls) -> "WorldStamp":
        return cls(_world_stamp_value(), _current_epoch())

    def is_current(self) -> bool:
        """Cheap validity probe (no raise): epoch int first — every
        programmatic change bumps it — then the raw env fingerprint
        (storage-only flags excluded)."""
        return (self.epoch == _current_epoch()
                and self.stamp == _world_stamp_value())

    def describe_staleness(self) -> Optional[str]:
        """Human-readable account of what moved (``None`` if current)."""
        cur_epoch = _current_epoch()
        if self.epoch != cur_epoch:
            return (f"the elastic communication epoch advanced "
                    f"({self.epoch} -> {cur_epoch}): the world this "
                    "program was compiled for was revoked (shrink, grow, "
                    "or drain)")
        cur = _world_stamp_value()
        if self.stamp == cur:
            return None
        old_env, new_env = self.stamp[1], cur[1]
        changed = [name for name, a, b in
                   zip(_WORLD_FLAG_NAMES, old_env, new_env) if a != b]
        if changed:
            return ("configuration flag(s) changed since the pin: "
                    + ", ".join(changed))
        return ("the configuration epoch moved (a set_* override was "
                "applied since the pin)")

    def check(self, what: str = "pinned program") -> None:
        """Raise :class:`StaleProgramError` (MPX129) unless current."""
        why = None
        if not self.is_current():
            why = self.describe_staleness()
        if why is None:
            return
        raise mpx_error(
            StaleProgramError, "MPX129",
            f"{what} is stale: {why}.  A pinned executable does no "
            "per-call key work, so it cannot retrace itself — re-pin it "
            "(program.repin(), or a fresh mpx.compile) to pick up the "
            "new world; mpx.elastic.run re-pins step functions "
            "automatically (docs/aot.md)",
        )
