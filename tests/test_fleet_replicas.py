"""Four one-device read replicas behind one router, on four forced host
devices (a subprocess: the tests themselves run on one device).

One index is built and placed whole on each device.  The four-cell
router must answer every query with the ids a one-cell router over the
same index gives, and the same distances up to float32 rounding; every
cell must serve; the answers must be the exact float64 top-k where
every bucket is probed; each cell's ``collect`` and
``dispatch`` spans must name its own device, and the benchmark harness
must run a ``cells: 4`` configuration to a ``correct`` verdict."""
import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from conftest import REPO, subprocess_env

_BODY = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys, tempfile, warnings
warnings.filterwarnings("ignore")
from pathlib import Path
import numpy as np
sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "bench", "tests")]

from bench import harness, reference
from repro.core.two_level import TwoLevelConfig, build_two_level
from repro.launch.mesh import make_cell_meshes
from repro.launch.serve import Deployment, serve, submit_all, warm
from repro.obs.trace import Tracer, set_tracer
from tiny import make_root

rng = np.random.default_rng(7)
db = rng.normal(size=(2048, 32)).astype(np.float32)
queries = (db[rng.integers(0, 2048, 256)]
           + 0.1 * rng.normal(size=(256, 32))).astype(np.float32)
# every bucket probed: the two-level answer is the exact top-k
target = build_two_level(db, TwoLevelConfig(
    n_clusters=16, top="brute", bottom="brute", kmeans_iters=4,
    kmeans_minibatch=2048, seed=3))
dep = Deployment(kind="ivf", db=db, target=target, queries=queries,
                 nprobe=16, data_s=0.0, build_s=0.0)
out = {}
tracer = Tracer(capacity=1 << 18)
set_tracer(tracer)
for n in (4, 1):
    router = serve(make_cell_meshes(n, shape=(1,)), dep, k=10,
                   max_batch=16)
    try:
        warm(router, 32)
        d, i = submit_all(router, queries, clients=16)
        out[n] = {"d": d.tolist(), "i": i.tolist(),
                  "served": [int(c.metrics.get("batch_size").sum)
                             for c in router.cells],
                  "mesh": {c.name: [int(x.id) for x in
                                    c.search_fn.mesh.devices.flat]
                           for c in router.cells}}
        if n == 4:
            devs = {}
            for ev in tracer.events():
                if ev.get("ph") == "X" and ev["name"] in ("collect",
                                                           "dispatch"):
                    devs.setdefault(ev["args"]["cell"], set()).add(
                        json.dumps(ev["args"].get("device")))
            out["span_devices"] = {c: sorted(v) for c, v in devs.items()}
    finally:
        router.close()
ids, dists = reference.exact_topk(db, queries, 10)
out["exact"] = {"i": ids.tolist(), "d": dists.tolist()}
# the scale float32 rounding of a squared distance works at
q64, x64 = queries.astype(np.float64), db.astype(np.float64)
out["scale"] = ((q64 * q64).sum(1)[:, None]
                + (x64 * x64).sum(1)[ids]).tolist()

root = make_root(Path(tempfile.mkdtemp()))
cfg_path = root / "bench" / "configs" / "tiny-ivf.json"
cfg = json.loads(cfg_path.read_text())
cfg["serve"]["cells"] = 4
cfg_path.write_text(json.dumps(cfg))
bm = json.loads((root / "BENCHMARK.json").read_text())
for w in bm["workloads"]:
    if w["config"] == "tiny-ivf":
        w["chips"] = 4
(root / "BENCHMARK.json").write_text(json.dumps(bm))
run = harness.run("tiny-ivf.tiny-open", 2**31 + 11, 0.5, True, root=root,
                  require_chip=False, compile_cache=False,
                  log=lambda _: None)
out["run"] = {"correct": run["correct"], "attempted": run["attempted"],
              "failed": run["failed"], "count": run["device"]["count"]}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def replicas():
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(_BODY)],
                       capture_output=True, text=True, timeout=600,
                       env=subprocess_env(), cwd=REPO)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_four_replicas_answer_as_one_and_all_serve(replicas):
    """The same ids; the same distances up to float32 rounding, which
    differs with the batch a query shares (its dispatch's shape), not
    with the replica."""
    four, one = replicas["4"], replicas["1"]
    assert all(n > 0 for n in four["served"])
    assert sum(four["served"]) == sum(one["served"])
    np.testing.assert_array_equal(four["i"], one["i"])
    exact, scale = replicas["exact"], np.asarray(replicas["scale"])
    np.testing.assert_array_equal(four["i"], exact["i"])
    eps = 16 * np.finfo(np.float32).eps
    for got, want in ((four["d"], one["d"]), (four["d"], exact["d"])):
        gap = np.abs(np.asarray(got) - np.asarray(want)) / scale
        assert gap.max() <= eps


def test_each_replica_spans_name_its_own_device(replicas):
    mesh = replicas["4"]["mesh"]
    assert sorted(d for ids in mesh.values() for d in ids) == [0, 1, 2, 3]
    spans = replicas["span_devices"]
    assert sorted(spans) == sorted(mesh)
    for cell, ids in mesh.items():
        assert spans[cell] == [json.dumps(ids[0])]


def test_the_harness_runs_a_four_cell_configuration_correctly(replicas):
    run = replicas["run"]
    assert run["count"] == 4
    assert run["correct"] is True
    assert run["failed"] == 0 and run["attempted"] > 0
