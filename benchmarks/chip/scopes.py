"""Which program scope each device op of the train step belongs to.

The train step wraps its layers in ``jax.named_scope("asteroid/<name>")``
(``repro.scopes``).  The names land in the compiled HLO's ``op_name``
metadata, under the ``jvp``, ``transpose`` and ``checkpoint`` wrappers of
autodiff and remat; the last ``asteroid/`` component of an instruction's
``op_name`` is its innermost scope.  The instruction names of the compiled
text are those the chip's trace prints, so ``scope_map`` puts each device
op of a trace down to a program layer; ``trace.reduce`` sums the device
time of each scope with it.

JAX's persistent compile cache leaves metadata out of its key: an entry
compiled by a build without the scopes hands back text that names none, so
each tree keeps a cache of its own.  A traced run of the train job stops
where the text names no scope, or where the scopes' device time falls under
``SCOPED_FLOOR`` of a chip's busy time (instruction names that do not match
the trace's): the scope metrics would then read low or not at all.
"""

from __future__ import annotations

import dataclasses
import re

SCOPE = re.compile(r"asteroid/(\w+)")
# one instruction of HLO text: its name, then (on the same line) metadata
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?"
                         r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
MODULE = re.compile(r"^HloModule ([\w.\-]+)")
# the least share of a chip's busy time its scopes may cover: all but the
# few copies XLA adds without metadata (97-98% on the chip)
SCOPED_FLOOR = 0.9


def innermost(op_path: str) -> str | None:
    """The last ``asteroid/<name>`` of an ``op_name`` path, or None."""
    found = SCOPE.findall(op_path)
    return found[-1] if found else None


@dataclasses.dataclass
class ScopeMap:
    module: str            # the HLO module's name (``jit_step_fn``)
    scopes: dict           # instruction name -> innermost scope or None


def scope_map(hlo_text: str) -> ScopeMap:
    """Instruction name to innermost scope, from compiled HLO text."""
    m = MODULE.search(hlo_text)
    if m is None:
        raise ValueError("no HloModule line in the HLO text")
    scopes = {}
    for line in hlo_text.splitlines():
        hit = INSTRUCTION.match(line)
        if hit:
            scopes[hit.group(1)] = innermost(hit.group(2))
    return ScopeMap(m.group(1), scopes)


def compiled_text(jitted, *args) -> str:
    """The compiled HLO text of ``jitted`` for arguments shaped, laid out
    and donated like ``args`` (which may already be donated: only their
    shapes, dtypes and shardings are read)."""
    import jax

    def abstract(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)

    return jitted.lower(*jax.tree.map(abstract, args)).compile().as_text()
