"""Device programs: the data plane's jitted XLA programs — the fused stage
chain and its sink (``fused_chain``), built on the hash probe
(``hash_probe.probe_slots``) — and the LM stack's Pallas kernels
(attention, linear recurrence)."""

import jax


def default_interpret() -> bool:
    """Pallas kernels compile on the TPU and run interpreted elsewhere."""
    return jax.default_backend() != "tpu"
