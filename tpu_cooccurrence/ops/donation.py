"""Backend-gated buffer donation for the state-carrying jits.

Donation (``jax.jit(donate_argnums=...)``) is what lets every scorer
update its device-resident state (dense ``C``, the sparse slab, the
deferred-results table) in place: without it XLA allocates a fresh output
buffer and copies — at the 1M-item shapes that is gigabytes of HBM traffic
per window.

On the **CPU backend** donation is disabled here, deliberately. The
jaxlib 0.4.36 TFRT CPU runtime has a donation/async-dispatch race: a
donating dispatch can acquire a buffer that an earlier, still-executing
computation is reading, which surfaces as ``Check failed:
pending_donation_`` (abstract_tfrt_cpu_buffer.cc) or — worse — as silent
glibc heap corruption ("corrupted double-linked list" at some later
``free``). Reproduced deterministically by the checkpoint/restore tests:
after a restore the jit cache is warm, so back-to-back windows dispatch
fast enough to race the in-flight score reads of the just-donated count
matrix. The copy this costs on CPU is host-memory bandwidth — real but
bounded — where the race is a crash; accelerator backends keep full
donation (their PJRT clients sequence donation against pending reads
correctly).
"""

from __future__ import annotations

from typing import Tuple


def donate_argnums(*argnums: int) -> Tuple[int, ...]:
    """``argnums`` on accelerator backends, ``()`` on CPU (see module doc).

    Evaluated at decoration time (module import), which for every scorer
    module happens lazily inside the job's backend factory. The
    ``jax.default_backend()`` probe initializes the local backend, so
    import order matters for multi-host: ``job._make_scorer`` runs
    ``jax.distributed.initialize`` (via ``maybe_multihost_mesh``)
    *before* importing any scorer module — a scorer import that
    initialized the backend first would make distributed init raise.
    """
    import jax

    return tuple(argnums) if jax.default_backend() != "cpu" else ()
