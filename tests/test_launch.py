"""The serving launcher's functions (what ``chip_smoke.py`` drives on the
chip) at a CPU size: build -> place through the fleet -> serve through the
router -> recall against the float64 oracle."""
import numpy as np
import pytest

from repro.launch import serve as L
from repro.launch.mesh import make_cell_meshes


def test_exact_topk_matches_sorted_full_scan():
    rng = np.random.default_rng(0)
    db = rng.normal(size=(300, 8)).astype(np.float32)
    q = rng.normal(size=(5, 8)).astype(np.float32)
    got = L.exact_topk(db, q, 7, chunk=64)          # several chunk merges
    d2 = ((q[:, None, :].astype(np.float64) - db[None]) ** 2).sum(-1)
    assert np.array_equal(got, np.argsort(d2, axis=1, kind="stable")[:, :7])


@pytest.mark.parametrize("arch,kind,precision,bar", [
    ("sift-1m", "ivf", "f32", 0.8),
    ("sift-1m", "brute", "f32", 0.99),
    ("sift-1m", "brute", "int8", 0.9),
    ("radio-station", "forest", "f32", 0.6),
])
def test_served_deployment_recall(arch, kind, precision, bar):
    from repro.core.metrics import recall_at_k

    dep = L.build_deployment(arch, kind, scale=0.005, n_requests=48)
    router = L.serve(make_cell_meshes(1), dep, precision=precision,
                     max_batch=16)
    try:
        assert L.warm(router, dep.db.shape[1]) > 0
        d, ids = L.submit_all(router, dep.queries, clients=8)
    finally:
        router.close()
    assert d.shape == ids.shape == (48, 10)
    assert np.isfinite(d).all() and (ids >= 0).all()
    assert recall_at_k(ids, L.exact_topk(dep.db, dep.queries, 10)) >= bar
    backend = router.cells[0].search_fn
    if kind != "brute":
        # the config's nprobe is global: one chip probes all of it
        assert backend.nprobe_local == dep.nprobe
