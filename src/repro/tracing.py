"""The names the profiler shows for ARCHES: slot stages and host spans.

One mechanism, ``jax.profiler``'s, in two places:

* **Device.** Each stage of the closed-loop slot runs under one
  ``jax.named_scope`` (``stage``).  A scope is compile-time metadata: it
  adds no device op, and it lands in every op's ``op_name`` path
  (``jit(_closed_slot_step)/.../arches.tx/...``), so a profiler trace can
  give each device op to a stage.  An op's stage is the innermost
  ``arches.<stage>`` component of that path, whatever transform wraps it.
* **Host.** A span is a ``jax.profiler.TraceAnnotation``: it records only
  while a profiler session is active, and costs under a microsecond
  otherwise, so there is no switch to turn it on.
"""

from __future__ import annotations

import jax

#: the slot's stages, in the order a slot runs them
TX = "arches.tx"  # slot keys, link adaptation, payload bits, QAM, grid
CHANNEL = "arches.channel"  # fading, interference, noise, cell coupling
RX = "arches.rx"  # LS estimate, equalizer, data-RE extraction
EXPERTS = "arches.experts"  # the expert bank and its switch
KPM = "arches.kpm"  # EVM, TB model, OLLA, the KPM report
DECIDE = "arches.decide"  # KPM window, policy, register, breaker, packing
STAGES = (TX, CHANNEL, RX, EXPERTS, KPM, DECIDE)

#: host span around one call of the compiled closed-loop slot step
SLOT_DISPATCH = "arches.slot.dispatch"


def stage(name: str):
    """The device scope of one slot stage (one of ``STAGES``)."""
    return jax.named_scope(name)
