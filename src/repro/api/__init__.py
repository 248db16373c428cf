"""Unified solver API: declarative `SamplerSpec` -> compiled `Session`.

The single entry point every workload uses to construct samplers:

    spec = api.SamplerSpec(graph=g, hw=hw, mismatch=mism,
                           noise="counter", backend="auto",
                           schedule=api.Anneal(0.05, 3.0, n_sweeps=600),
                           chains=64)
    session = api.Session(spec)       # env + backend resolved HERE, once
    chip = session.program(J_codes, h_codes)
    state = session.init_state(key)
    m, ns, _ = session.sample(chip, state.m, state.noise_state)

See docs/api.md for the lifecycle and the old-call -> new-call migration
table; `core.cd.PBitMachine.session(...)` builds specs/sessions from the
familiar machine object.
"""
from repro.api.faults import Faults, sample_faults
from repro.api.program import Program, stack_programs
from repro.api.spec import (
    BACKENDS,
    FUSED_BACKENDS,
    IN_KERNEL_NOISE,
    NOISE_KINDS,
    SPARSE_BACKENDS,
    Anneal,
    Constant,
    Partition,
    SamplerSpec,
    Schedule,
    Sync,
    Tempered,
    dense_vmem_feasible,
    resolve_backend,
    resolve_interpret,
    spec_fingerprint,
)
from repro.api.session import (
    CD_METRICS,
    Session,
    SessionState,
    program,
    program_chip,
    program_edges,
    program_master,
)

__all__ = [
    "BACKENDS", "FUSED_BACKENDS", "IN_KERNEL_NOISE", "NOISE_KINDS",
    "SPARSE_BACKENDS",
    "Schedule", "Constant", "Anneal", "Tempered",
    "Partition", "Sync", "SamplerSpec", "Session", "SessionState",
    "CD_METRICS",
    "Faults", "sample_faults", "Program", "stack_programs",
    "program", "program_chip", "program_edges", "program_master",
    "dense_vmem_feasible", "resolve_backend", "resolve_interpret",
    "spec_fingerprint",
]
