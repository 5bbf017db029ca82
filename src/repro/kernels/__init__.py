"""Device programs of the data plane: XLA programs for the probes and the
fused stage chain, Pallas kernels for the opt-in batch insert and segmented
aggregate (and the LM stack's attention/recurrence)."""

import jax


def default_interpret() -> bool:
    """Pallas kernels compile on the TPU and run interpreted elsewhere."""
    return jax.default_backend() != "tpu"
