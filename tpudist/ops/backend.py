"""The one place a Pallas kernel learns how to run on this backend."""

import jax


def interpret() -> bool:
    """``pallas_call``'s ``interpret=``: Mosaic-compile on a TPU, interpret
    on the CPU (tests, emulated meshes). Any other platform raises — a
    platform string that is merely "not tpu" must not interpret silently."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"tpudist's Pallas kernels run compiled on 'tpu' or interpreted on "
        f"'cpu'; the default backend is {platform!r}"
    )
