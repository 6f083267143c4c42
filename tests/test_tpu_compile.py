"""The five routing kernels compile for a TPU v5e at real widths.

Mosaic refuses what interpret mode accepts (float iotas, unaligned column
slices, blocks that break the (8, 128) tiling, VMEM overflow), so every
kernel on the served path is compiled here, through its `ops` wrapper,
for one chip of a described ``v5e:2x2`` topology.  Nothing runs: the
compile needs only shapes.  Widths:

* served — 16,384 tools on 16,384 servers (the ~16k APIs of ToolBench,
  arXiv:2307.16789), a 64-sample telemetry window;
* mega — the 10^6-server tiled fleet: 2M tools, a 32-sample window;
* shard — one of 4 mesh shards of that fleet: 500k tools, 250k servers.

Each width also comes as the engine stores it (``-aligned``): the corpus
at the kernel's aligned shape, its real count below its rows; the compiled
program must not pad or copy it.

16 queries, V=123 terms, top_s=8, k=16.  Stage 1 over the tiled pool
(`stage1_select`, and the engine's whole route program around it) is
compiled at the served chunk: 8 queries, 15 templates, s_keep 8, 10^6
replicas.  The topology is described in a
fixture, so only the worker that runs these tests loads the TPU library;
the persistent compile cache is off around the compiles (a compile for
a described chip cannot be read back without one).
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels import score_fuse as scf
from repro.kernels import stage1_select as s1

NQ, V, TOP_S, K = 16, 123, 8, 16
# name -> (n_tools, n_servers, telemetry window)
WIDTHS = {
    "served": (16_384, 16_384, 64),
    "mega": (2_000_000, 1_000_000, 32),
    "shard": (500_000, 250_000, 32),
}
F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32
# each width with its corpus at the natural shape, and as the engine stores
# it: already at the kernel's aligned shape, the real count below its rows
CASES = ([pytest.param(w, False, id=w) for w in WIDTHS]
         + [pytest.param(w, True, id=f"{w}-aligned") for w in WIDTHS])
SHORT = 37   # real rows short of the width in an aligned case


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype=F32: jax.ShapeDtypeStruct(dims, dtype,
                                                        sharding=one_chip)


def _assert_kernel(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def _assert_no_pad(text: str, corpus: jax.ShapeDtypeStruct) -> None:
    """No pad or copy instruction of the compiled program reads the corpus
    parameter."""
    dt = {"float32": "f32", "bfloat16": "bf16"}[corpus.dtype.name]
    dims = ",".join(map(str, corpus.shape))
    names = re.findall(rf"%(\S+) = {dt}\[{dims}\]\S* parameter\(", text)
    assert len(names) == 1, names
    reads = re.compile(rf"%{re.escape(names[0])}[,)]")
    pads = re.compile(r"^%\S*(pad|copy)\S* = | (pad|copy)\(")   # op or fusion
    hits = [ln.strip()[:160] for ln in text.splitlines()
            if reads.search(ln) and pads.search(ln.strip())]
    assert not hits, hits


@pytest.mark.parametrize("width,aligned", CASES)
def test_bm25_compiles(shape, width, aligned):
    n_t = WIDTHS[width][0]
    if not aligned:
        _assert_kernel(lambda q, w: ops.bm25_scores(q, w, interpret=False),
                       shape((NQ, V)), shape((n_t, V)))
        return
    n_d = n_t - SHORT
    w = shape(ops.bm25_corpus_shape(n_d, V))
    text = _assert_kernel(
        lambda q, w: ops.bm25_scores(q, w, n_docs=n_d, interpret=False),
        shape((NQ, V)), w)
    _assert_no_pad(text, w)


@pytest.mark.parametrize("width", list(WIDTHS))
def test_qos_compiles(shape, width):
    _, n_s, window = WIDTHS[width]
    _assert_kernel(lambda lat: ops.qos_scores(lat, interpret=False),
                   shape((n_s, window)))


@pytest.mark.parametrize("width", list(WIDTHS))
def test_fused_select_compiles(shape, width):
    """Static weights with load and a per-query dead mask (SONAR-FT)."""
    n_t = WIDTHS[width][0]
    _assert_kernel(
        lambda s, v, q, u, d: ops.fused_select(
            s, v, q, u, d, k=K, alpha=0.5, beta=0.5, gamma=0.35,
            interpret=False),
        shape((NQ, n_t)), shape((NQ, n_t)), shape((n_t,)), shape((n_t,)),
        shape((NQ, n_t)))


@pytest.mark.parametrize("width", ["served", "shard"])
def test_fused_select_live_weights_compiles(shape, width):
    """SONAR-ADAPT: the fusion weights arrive as traced data."""
    n_t = WIDTHS[width][0]
    _assert_kernel(
        lambda s, v, q, w: ops.fused_select(
            s, v, q, k=K, alpha=w[0], beta=w[1], gamma=w[2], delta=w[3],
            interpret=False),
        shape((NQ, n_t)), shape((NQ, n_t)), shape((n_t,)), shape((4,)))


@pytest.mark.parametrize("width,aligned", CASES)
def test_fused_score_select_compiles(shape, width, aligned):
    """bf16 tool weights, load and a per-query dead mask (SONAR-FT)."""
    n_t = WIDTHS[width][0]
    w_shape = (n_t, V)
    if aligned:
        n_t -= SHORT
        w_shape = ops.score_fuse_corpus_shape(n_t, V)
    w = shape(w_shape, BF16)
    text = _assert_kernel(
        lambda q, w, ts, c, qs, u, d: ops.fused_score_select(
            q, w, ts, c, qs, u, d, k=K, alpha=0.5, beta=0.5, gamma=0.35,
            interpret=False),
        shape((NQ, V)), w, shape((n_t,), I32),
        shape((NQ, TOP_S), I32), shape((n_t,)), shape((n_t,)),
        shape((NQ, n_t)))
    if aligned:
        _assert_no_pad(text, w)


@pytest.mark.parametrize("width", ["served", "mega"])
def test_fused_score_select_live_weights_affinity_compiles(shape, width):
    """SONAR-ADAPT weights as data plus a per-query affinity row."""
    n_t = WIDTHS[width][0]
    _assert_kernel(
        lambda q, w, ts, c, qs, a, wv: ops.fused_score_select(
            q, w, ts, c, qs, k=K, alpha=wv[0], beta=wv[1], gamma=wv[2],
            tool_aff=a, eps=0.25, interpret=False),
        shape((NQ, V)), shape((n_t, V)), shape((n_t,), I32),
        shape((NQ, TOP_S), I32), shape((n_t,)), shape((NQ, n_t)),
        shape((4,)))


def test_stripe_flags_smem_fits_large_batch(shape):
    """The stripe flags ride in SMEM one query tile's row at a time, so
    the footprint is n_stripes words whatever the batch: 1,024 queries on
    the 2M-tool fleet compile (the kernel alone, flags given)."""
    n_q, n_t = 1024, WIDTHS["mega"][0]
    v_pad, n_st = 128, n_t // scf.STRIPE + (n_t % scf.STRIPE > 0)
    t_pad = n_st * scf.STRIPE
    row = shape((1, t_pad))
    _assert_kernel(
        lambda q, w, h, c, r, f: scf.fused_score_select_pallas(
            q, q, w, h, c, r, r, r, r, f, k=K, top_s=TOP_S, alpha=0.5,
            beta=0.5, gamma=0.0, delta=0.0, temp=1.0, rerank=False,
            per_query_qos=False, per_query_load=False, per_query_rtt=False,
            per_query_dead=False),
        shape((n_q, v_pad)), shape((t_pad, v_pad), BF16),
        shape((1, t_pad), I32), shape((n_q, TOP_S), I32), row,
        shape((n_q // scf.QUERY_TILE, n_st), I32))


@pytest.mark.parametrize("dead_rows", [8, 1], ids=["per-query", "shared"])
def test_stage1_select_compiles(shape, dead_rows):
    """Stage 1 over the 10^6-replica tiled shard: the template map stored
    at the kernel's width, the dead row at the engine's (unaligned)."""
    n = WIDTHS["mega"][1]
    _assert_kernel(
        lambda s, m, d: ops.stage1_select(s, m, d, k=8, interpret=False),
        shape((8, 15)), shape((1, 1, s1.map_width(n)), I32),
        shape((1, dead_rows, n)))


def test_tiled_route_sorts_no_replica_row(shape):
    """The pool's route program (SONAR-FT over the tiled 10^6-replica
    index, the sharded engine's kernel path, one 8-query chunk with its
    health row) runs stage 1 in `stage1_select` and holds no sort with a
    dimension of 10^5 or more."""
    import numpy as np

    from repro.core.mesh_routing import ShardedRoutingEngine
    from repro.core.routing import RoutingConfig
    from repro.traffic import fleet

    n = WIDTHS["mega"][1]
    eng = ShardedRoutingEngine(
        cfg=RoutingConfig(top_s=8, top_k=8), algo="sonar_ft",
        index=fleet.mega_fleet_index(n, weights_dtype="bfloat16"),
        use_kernels=True, interpret=False,
    )
    fn, args, kw = eng._program(
        eng.encode(["search the web for the latest news"] * 8),
        server_load=np.zeros(n, np.float32),
        failed_mask=np.zeros((8, n), bool),
        telemetry_templates=(np.zeros((16, 64), np.float32),
                             np.arange(n) % 16),
    )
    args = jax.tree.map(lambda a: shape(a.shape, a.dtype), args)
    text = fn.lower(*args, **kw).compile().as_text()
    assert re.search(r"%stage1_select\S* = .* custom-call\(", text)
    sorts = re.findall(r"= \(?([^=]*?)\)? sort\(", text)
    big = [s for s in sorts
           if any(int(d) >= 100_000 for d in re.findall(r"\d+", s))]
    assert not big, big
