"""Runtime chip programs: the weight-streaming operand.

On silicon, reprogramming is an SPI write of DAC codes — milliseconds,
never a recompiled circuit.  A `Program` is the software twin: the full
runtime description of one programmed problem (edge codes, bias codes,
optional clamps, optional per-chip mismatch draw, optional schedule),
registered as a jax pytree so a compiled `api.Session` closure takes it
as an *argument*.  One executable per (graph-shape, partition, sync,
backend, noise) bucket then serves every program bit-exactly:

    prog = session.make_program(J_codes, h_codes)
    m, ns, _ = session.sample_program(prog, m, ns, betas)   # zero retrace

Swapping problems is a host->device copy of O(E) codes, not an XLA
compile — the benchmark cell `chip440.sample` sends a fresh program
every call.  Stacking programs along a leading axis
(`stack_programs`) gives the **fleet axis**: `Session.sample_fleet`
vmaps one executable over K mismatch draws / tenants / CD replicas.

The optional ``mismatch`` field carries a per-program chip-instance draw
(same type as the spec's — `Mismatch` or `SparseMismatch`).  ``None``
means "use the Session spec's draw"; a value makes the process variation
itself a runtime operand, which is what lets a virtual-chip fleet share
one compiled step (see `core/cd.py::make_cd_fleet_step`).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Program:
    """One runtime chip program — every field is a pytree leaf (or None).

    ``J_codes``/``h_codes`` are signed 8-bit DAC codes in the edge-list
    layout ((E,) / (N,)); clamp fields follow `Session.sample`'s
    contract ((N,) bool mask, (B, N) values); ``betas`` optionally
    carries the program's own (S,) or (S, B) schedule; ``mismatch``
    optionally overrides the spec's chip-instance draw.  Optional fields
    left ``None`` are structurally absent, so presence/absence selects
    the (cached) trace — values never do.

    Leaves may carry a leading fleet axis (K, ...) — see
    `stack_programs` and `Session.sample_fleet`.
    """

    J_codes: jax.Array
    h_codes: jax.Array
    mismatch: object | None = None
    clamp_mask: jax.Array | None = None
    clamp_values: jax.Array | None = None
    betas: jax.Array | None = None

    def tree_flatten(self):
        f = dataclasses.fields(self)
        return tuple(getattr(self, x.name) for x in f), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def stack_programs(programs) -> Program:
    """Stack same-structure programs along a new leading fleet axis.

    Every program must carry the same optional-field structure (all have
    clamps or none do, all carry a mismatch or none does) — the fleet
    runs one trace, so structure cannot vary across its members.
    Returns a `Program` whose every leaf has shape (K, ...), ready for
    `Session.sample_fleet` / `Session.make_cd_fleet_step`.
    """
    programs = list(programs)
    if not programs:
        raise ValueError("stack_programs needs at least one program")
    ref = jax.tree_util.tree_structure(programs[0])
    for k, p in enumerate(programs[1:], 1):
        if jax.tree_util.tree_structure(p) != ref:
            raise ValueError(
                f"program {k} has a different optional-field structure "
                f"than program 0; a fleet shares one trace, so every "
                f"member must carry the same fields")
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *programs)
