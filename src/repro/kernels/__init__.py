# Pallas TPU kernels for the paper's compute hot-spots.
#
# Each subpackage has:
#   <name>.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
#   ops.py    — the public wrapper; picks interpret vs compiled via interpret()
#   ref.py    — pure-jnp oracle used by the allclose test sweeps
#
# Mapping to the paper (DESIGN.md §8):
#   histogram       — §4.1 local statistics K^(i) (the communication mechanism)
#   sketch_hist     — count-min compressed statistics (stats="sketch")
#   segment_reduce  — the Reduce "sort"+"run" phase over bucket-file layout (§4.4)
#   moe_dispatch    — the shuffle "copy": counting-sort of tokens by slot
#   flash_attention — keeps train_4k/prefill_32k compute-bound (roofline)

import jax


def interpret() -> bool:
    """How the ``ops`` wrappers run their Pallas kernels on this backend.

    ``cpu`` runs the Pallas interpreter (tests, CPU rehearsals); ``tpu``
    compiles with Mosaic. Any other backend raises: a kernel path never
    degrades silently to the interpreter on an accelerator.
    """
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run interpreted on 'cpu' or compiled on 'tpu'; "
        f"the default backend is {backend!r}"
    )
