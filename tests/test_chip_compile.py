"""Compile-only checks for one TPU v5e chip, at main-path shapes.

The TPU compiler is installed without the chip: a ``v5e:2x2`` topology
can be described and programs compiled for one of its chips from shapes
alone. Nothing runs, so these tests say nothing about results or time;
they catch what interpret mode cannot — block shapes the Mosaic tiling
rule refuses, ops Mosaic does not lower, SMEM and HBM overflow.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and each test worker
imports every test file.

Shapes: TGN (fanout (10,), ``recent``, batch 4000 -> 12,000 seeds) and
TGAT (fanouts (10, 10), ``uniform``, batch 600 -> 1,800 seeds, 18,000
second-hop targets) at the published widths of configs/tgn_gdelt.py,
over a JODIE-Wikipedia-sized graph: 9,228 node rows, 65,536 pages of
128 lanes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.tgn_gdelt import tgat, tgn
from repro.core.continuous import ContinuousTrainer
from repro.core.sampling import _sample_khop
from repro.data.events import synth_ctdg
from repro.kernels.cache_gather.ops import cache_gather_pallas
from repro.kernels.temporal_attn.ops import temporal_attn_pallas
from repro.kernels.temporal_sample.ops import temporal_sample_pallas

HBM_BYTES = 16e9            # one v5e chip
NODES, PAGES, LANES, SCAN = 9_228, 65_536, 128, 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back here
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes
            + m.generated_code_size_in_bytes)
    assert used <= HBM_BYTES, f"{used / 1e9:.2f} GB > 16 GB"
    return compiled


def _mirror(sh):
    return dict(page_table=_sds(sh, (NODES, SCAN), jnp.int32),
                pages_nbr=_sds(sh, (PAGES, LANES), jnp.int32),
                pages_eid=_sds(sh, (PAGES, LANES), jnp.int32),
                pages_ts=_sds(sh, (PAGES, LANES)),
                pages_valid=_sds(sh, (PAGES, LANES), jnp.bool_))


def _is_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("cfg", [tgn(), tgat()], ids=["tgn", "tgat"])
def test_fused_sampler_compiles(one_chip, cfg):
    n = 3 * cfg.batch_size
    _fits(_sample_khop.lower(
        _mirror(one_chip), _sds(one_chip, (n,), jnp.int32),
        _sds(one_chip, (n,)), _sds(one_chip, (n,), jnp.bool_),
        _sds(one_chip, (2,), jnp.uint32), fanouts=cfg.fanouts,
        policy=cfg.sampling, window=cfg.window, scan_pages=SCAN,
        use_pallas=False).compile())


def test_tgn_train_step_compiles(one_chip):
    """The trainer's own jitted step, at published widths and batch
    4000: a tiny batch staged on the CPU gives the batch's structure,
    every leaf's leading dim scales with the batch size."""
    small = 8
    cfg = tgn(batch_size=small)
    stream = synth_ctdg(n_nodes=60, n_events=200, d_node=cfg.d_node,
                        d_edge=cfg.d_edge, seed=0)
    tr = ContinuousTrainer(cfg, stream, seed=0)
    tr.ingest(stream.slice(0, 150))
    ev = stream.slice(150, 150 + small)
    batch = tr.assembler.finalize(tr._stage_batch(ev.src, ev.dst, ev.ts))
    scale = tgn().batch_size // small

    def big(x):
        return _sds(one_chip, (x.shape[0] * scale,) + x.shape[1:],
                    x.dtype)

    def place(x):
        return _sds(one_chip, x.shape, x.dtype)

    _fits(tr._train_step.lower(jax.tree.map(place, tr.params),
                               jax.tree.map(place, tr.opt_state),
                               jax.tree.map(big, batch)).compile())


@pytest.mark.parametrize("policy,n", [("recent", 12_000),
                                      ("uniform", 18_000)])
def test_temporal_sample_kernel_compiles(one_chip, policy, n):
    kw = {"rng_key": _sds(one_chip, (2,), jnp.uint32)} \
        if policy == "uniform" else {}
    m = _mirror(one_chip)
    c = temporal_sample_pallas.lower(
        m["page_table"], _sds(one_chip, (PAGES,)), _sds(one_chip, (PAGES,)),
        m["pages_nbr"], m["pages_eid"], m["pages_ts"], m["pages_valid"],
        _sds(one_chip, (n,), jnp.int32), _sds(one_chip, (n,)),
        _sds(one_chip, (n,)), _sds(one_chip, (n,), jnp.bool_), k=10,
        policy=policy, **kw).compile()
    _is_kernel(_fits(c))


@pytest.mark.parametrize("rows,dim,ids", [(277, 128, 12_000),
                                          (4_724, 172, 131_072)],
                         ids=["node", "edge"])
def test_cache_gather_kernel_compiles(one_chip, rows, dim, ids):
    """3% caches (GNNFlow §6) of the node and edge tables; the edge
    probe covers one TGN batch's 120,000 sampled edges, which spans
    two SMEM-sized chunks."""
    c = cache_gather_pallas.lower(
        _sds(one_chip, (157_475,), jnp.int32),
        _sds(one_chip, (rows,), jnp.int32), _sds(one_chip, (rows, dim)),
        _sds(one_chip, (ids,), jnp.int32)).compile()
    _is_kernel(_fits(c))


@pytest.mark.parametrize("n", [12_000, 18_000], ids=["tgn", "tgat"])
def test_temporal_attn_kernel_compiles(one_chip, n):
    cfg = tgn()
    H, dh, k = cfg.n_heads, cfg.d_hidden // cfg.n_heads, 10
    c = temporal_attn_pallas.lower(
        _sds(one_chip, (n, H, dh)), _sds(one_chip, (n, k, H, dh)),
        _sds(one_chip, (n, k, H, dh)),
        _sds(one_chip, (n, k), jnp.bool_)).compile()
    _is_kernel(_fits(c))


def test_kernels_interpret_on_cpu():
    """On the CPU backend the same wrappers run the interpreter (what
    every other kernel test relies on); the platform, not a flag,
    chooses."""
    q = jnp.asarray(np.random.default_rng(0).normal(size=(8, 2, 4)),
                    jnp.float32)
    text = temporal_attn_pallas.lower(
        q, jnp.stack([q] * 3, 1), jnp.stack([q] * 3, 1),
        jnp.ones((8, 3), bool)).as_text()
    assert "tpu_custom_call" not in text
