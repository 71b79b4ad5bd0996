import contextlib
import functools
import os
import sys

# tests are CPU-sized and see ONE CPU device (dry-run forces 512 in its own
# process); what runs on the chip is checked by chip_smoke.py and by the
# described-v5e compiles in test_chip_compile.py
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__)))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def subprocess_env():
    """Env for subprocess tests that re-import JAX with their own XLA_FLAGS.

    ``JAX_PLATFORMS=cpu`` selects the CPU backend the tests are sized
    for; with a TPU plugin installed JAX would otherwise start on the TPU
    (or, on a host without one, look for it before falling back).
    """
    return {
        "PYTHONPATH": os.path.join(REPO, "src"),
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "HOME": os.environ.get("HOME", "/root"),
        "JAX_PLATFORMS": "cpu",
    }


_KERNEL_OPS = ("l2_topk_op", "l2_topk_int8_op", "candidate_topk_op",
               "bm25_topk_op", "hybrid_topk_op")


@contextlib.contextmanager
def pallas_kernels(monkeypatch):
    """Inside the block the sharded scans' ``repro.kernels.ops``
    dispatchers run the Pallas kernel body (interpret mode off the chip)
    instead of the jnp reference.  A jitted search traces its dispatch at
    its first call, so call a backend's search inside the block to put
    the kernel body in its program."""
    from repro.kernels import ops

    with monkeypatch.context() as m:
        for name in _KERNEL_OPS:
            m.setattr(ops, name,
                      functools.partial(getattr(ops, name), force_pallas=True))
        yield
