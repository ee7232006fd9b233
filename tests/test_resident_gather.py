"""Subgraph batches gathered on the device from the session's resident
feature matrix: the same logits to the bit as the host-padded features, the
features of the version a batch was staged under, the counter that shows the
path engages, and the extract-purity watchdog around it."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.graphs.datasets import make_dataset
from repro.models import gnn
from repro.serve import GNNServeEngine, GraphStore, ShardedServeEngine
from repro.serve.session_core import PreparedGroup, StagedBatch
from repro.serve.trace import SpanTracer, TransferWatchdog

jax.config.update("jax_platform_name", "cpu")

HIDDEN = 16
BATCH = 8
FAMILIES = ("gcn", "sage", "saint")
INIT = dict(gcn=gnn.init_gcn, sage=gnn.init_sage, saint=gnn.init_saint)


def _store(seed=0):
    data = make_dataset("cora", seed=seed, scale=0.1)
    st = GraphStore(max_batch=BATCH)
    st.register_graph("g", data)
    f, c = data.x.shape[1], data.n_classes
    for fam in FAMILIES:
        st.register_model(fam, fam,
                          INIT[fam](jax.random.PRNGKey(0), f, HIDDEN, c))
    return st, data


@pytest.fixture(scope="module")
def store_data():
    return _store()


def _extracted(session, rng, n_nodes):
    uniq = np.unique(rng.integers(0, n_nodes, size=BATCH))
    return session._extract(uniq)


@pytest.mark.parametrize("co_launch", [False, True])
@pytest.mark.parametrize("family", FAMILIES)
def test_resident_gather_matches_host_padding(store_data, family, co_launch):
    """A batch staged as row ids into the resident matrix returns the same
    logits, bit for bit, as the same batch staged with host-padded
    features, launched alone or co-launched; the padded ids gather exact
    zero rows."""
    st, data = store_data
    session = st.session("g", family)
    core, bn = session.core, session.bn
    rng = np.random.default_rng(11)
    pairs = []
    for _ in range(2 if co_launch else 1):
        sub_nodes, mats, seed_pos = _extracted(session, rng, data.n_nodes)
        host = core.stage(data.x[sub_nodes], mats, seed_pos)
        res = core.stage((session._x_dev, sub_nodes), mats, seed_pos)
        assert res.x_pad is None and res.resident is session._x_dev
        assert res.n_pad == host.n_pad
        assert (res.rows[sub_nodes.size:] == data.n_nodes).all()
        gathered = jnp.take(res.resident, jnp.asarray(res.rows), axis=0,
                            mode="fill", fill_value=0)
        assert np.array_equal(np.asarray(gathered), host.x_pad)
        pairs.append((host, res))
    r0 = core.resident_launches
    if co_launch:
        outs = [core.launch_many([(s[k], bn) for s in pairs])
                for k in (0, 1)]
        got = [[core.finish(o, s[k]) for o, s in zip(outs[k], pairs)]
               for k in (0, 1)]
    else:
        got = [[core.finish(core.launch(pairs[0][k], bn), pairs[0][k])]
               for k in (0, 1)]
    assert core.resident_launches == r0 + len(pairs)
    for want, have in zip(*got):
        assert np.array_equal(want, have)


@pytest.mark.parametrize("family", FAMILIES)
def test_staged_batch_answers_at_its_staging_version(family):
    """Staged, then a feature update and a sync, then launched: the batch
    answers with the features (and calibration) it was staged under."""
    st, data = _store(seed=1)
    session = st.session("g", family)
    seeds = np.random.default_rng(3).integers(0, data.n_nodes, size=BATCH)
    before = session.serve_subgraph(seeds)
    prepared = session.prepare_batch(seeds)
    x_new = np.asarray(
        np.random.default_rng(4).standard_normal(data.x.shape), np.float32)
    st.update_features("g", x_new)
    session.sync()
    assert prepared.groups[0].staged.resident is not session._x_dev
    after = session.serve_subgraph(seeds)
    assert not np.array_equal(before, after)
    staged_out = session.finish_batch(prepared,
                                      session.launch_batch(prepared))
    assert np.array_equal(staged_out, before)


@pytest.mark.parametrize("cfg", [
    dict(pipeline_depth=0),
    dict(pipeline_depth=2, multi_bucket=True),
    dict(shards=2),
])
def test_resident_launches_counts_single_host_batches(cfg):
    """Every single-host subgraph batch is gathered from the resident
    matrix, serial or co-launched; a sharded session stages host features
    and counts none."""
    st, data = _store(seed=2)
    cfg = dict(cfg)
    shards = cfg.pop("shards", None)
    if shards:
        engine = ShardedServeEngine(st, shards, max_batch=BATCH,
                                    mode="subgraph", staleness_s=600.0, **cfg)
    else:
        engine = GNNServeEngine(st, max_batch=BATCH, mode="subgraph", **cfg)
    engine.warmup("g", "gcn")
    cores = [c for s in engine._sessions()
             for c in getattr(s, "cores", None) or [s.core]]
    r0 = sum(c.resident_launches for c in cores)
    nodes = np.random.default_rng(5).integers(0, data.n_nodes, size=40)
    qs = engine.submit_many("g", "gcn", nodes)
    engine.run_until_drained()
    assert all(q.done for q in qs)
    snap = engine.snapshot()
    assert snap["bytes_uploaded"]["launch"] > 0
    assert snap["watchdogs"]["transfer"]["device_in_extract"] == 0
    if shards:
        assert snap["resident_launches"] == 0
        assert len(cores) == shards
    else:
        assert engine.metrics.batches > 0
        assert snap["resident_launches"] - r0 == engine.metrics.batches
    engine.close()


@pytest.mark.parametrize("rows_on_device", [False, True])
def test_transfer_watchdog_checks_rows_not_the_resident_matrix(
        store_data, rows_on_device):
    """The watchdog flags a device array where extract's output belongs
    (the row ids), and leaves the captured resident matrix alone."""
    st, data = store_data
    session = st.session("g", "gcn")
    prepared = session.prepare_batch(np.arange(BATCH))
    staged = prepared.groups[0].staged
    assert isinstance(staged.resident, jax.Array)
    if rows_on_device:
        staged = StagedBatch(x_pad=None, adjs=staged.adjs,
                             pos_pad=staged.pos_pad, n_seeds=staged.n_seeds,
                             rows=jnp.asarray(staged.rows),
                             resident=staged.resident)
        prepared.groups = [PreparedGroup(core=session.core,
                                         sel=prepared.groups[0].sel,
                                         staged=staged)]
    wd = TransferWatchdog(SpanTracer())
    wd.check_prepared(prepared)
    assert wd.device_in_extract == int(rows_on_device)
