"""The four routing kernels compile for a TPU v5e at real widths.

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

16 queries, V=123 terms, top_s=8, k=16.  The topology is described in a
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
