"""Compile the serve path's Pallas kernels for a TPU v5e that is described,
not attached, at the widths of the benchmark's configurations: phi3-mini
(32 q/kv heads of width 96, vocab 32064, 8 slots, 2048 positions) and
qwen2-7b (28 query heads over 4 kv heads of width 128, GQA group 7,
vocab 152064, 48 slots, 2560 positions); and the paged engine's decode
window and widest prefill at each cell's sizes, within one chip's memory.

Interpret mode on the CPU cannot see what the chip's compiler refuses:
blocks that break the (8, 128) tiling rule, more VMEM or SMEM than a
kernel may use, casts Mosaic does not lower.  Each test lowers the
kernel with ``interpret=False`` and checks that the compiled program
holds the Mosaic kernel (``tpu_custom_call``).  Nothing runs, so these
say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this module.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import cache_sim, decode_attention, ops, paged_attention
from repro.kernels import sampling
from repro.models import build_model
from repro.serve import PagedEngine

SLOTS, HEADS, KV_HEADS, HEAD_DIM = 8, 32, 32, 96
MAX_LEN, VOCAB, PAGE = 2048, 32064, 16
LAYERS = 3
# (slots, query heads, kv heads, head width, positions, vocabulary)
WIDTHS = {"phi3": (SLOTS, HEADS, KV_HEADS, HEAD_DIM, MAX_LEN, VOCAB),
          "qwen2h": (48, 28, 4, 128, 2560, 152064)}
# each cell's engine: arch, layers, slots, positions, widest prefill
CELLS = {"phi3": ("phi3-mini-3.8b", 32, 8, 1024, 1024),
         "qwen2h": ("qwen2-7b", 14, 48, 2560, 512)}
# what one v5e program may hold: the chip's 16 GiB less the runtime's
CHIP_BYTES = 15.75 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a TPU executable written to the persistent cache cannot be read
    # back without a chip; keep these compiles out of it
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


def _compile(fn, sharding, *shapes, donate=()):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn, donate_argnums=donate).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _dense(fused):
    bf = jnp.bfloat16
    cache = (SLOTS, MAX_LEN, KV_HEADS, HEAD_DIM)
    rows = [((SLOTS, KV_HEADS, HEAD_DIM), bf)] * 2 if fused else []
    return [((SLOTS, HEADS, HEAD_DIM), bf), (cache, bf), (cache, bf),
            *rows, ((SLOTS,), jnp.int32), ((), jnp.int32)]


def _paged(fused, widths, max_len=None):
    """Operands of the paged kernel: the fused variant takes the
    layer-stacked pools and a layer index, the plain one a layer's pool."""
    bf = jnp.bfloat16
    slots, heads, kv_heads, head_dim, positions, _ = WIDTHS[widths]
    nb = (max_len or positions) // PAGE
    pool = (slots * nb + 1, PAGE, kv_heads, head_dim)
    if fused:
        pool = (LAYERS,) + pool
    rows = [((slots, kv_heads, head_dim), bf)] * 2 if fused else []
    layer = [((), jnp.int32)] if fused else []
    return [((slots, heads, head_dim), bf), (pool, bf), (pool, bf), *rows,
            ((slots, nb), jnp.int32), ((slots,), jnp.int32),
            ((), jnp.int32), *layer]


@pytest.mark.parametrize("fused", [False, True], ids=["plain", "fused"])
def test_decode_attention_compiles(one_chip, fused):
    fn = (decode_attention.decode_attention_fused if fused
          else decode_attention.decode_attention)
    _compile(fn, one_chip, *_dense(fused))


@pytest.mark.parametrize("fused,widths,max_len", [
    (False, "phi3", None), (True, "phi3", None), (False, "qwen2h", None),
    (True, "qwen2h", None), (False, "phi3", 1024), (True, "phi3", 1024),
], ids=["plain", "fused", "plain-qwen2h", "fused-qwen2h", "plain-phi3-cell",
        "fused-phi3-cell"])
def test_paged_decode_attention_compiles(one_chip, fused, widths, max_len):
    """Each cell's production shapes: qwen2h 48 slots x 160 pages, phi3 8
    x 64 (its cell's 1024 positions) and 8 x 128.  At qwen2h's widths the
    kernel's page-row view of the pools, (ps * kv heads, head width) rows
    a page, is a bitcast: the program copies no pool-shaped array (the
    fused variant's stacks donated, as the decode scan hands them over).
    phi3's pools are not held to this: their entry layout puts the page
    axis minor, so the program copies them in and out around the kernel."""
    fn = (paged_attention.paged_decode_attention_fused if fused
          else paged_attention.paged_decode_attention)
    shapes = _paged(fused, widths, max_len)
    text = _compile(fn, one_chip, *shapes, donate=(1, 2) if fused else ())
    if widths == "qwen2h":
        pages, _, kv_heads, head_dim = shapes[1][0][-4:]
        pool = re.compile(
            rf"= bf16\[(\d+,)?{pages},({PAGE},{kv_heads}|{PAGE * kv_heads}),"
            rf"{head_dim}\]\S* (copy|copy-start|copy-done)\(")
        copies = [line.strip()[:120] for line in text.splitlines()
                  if pool.search(line)]
        assert not copies, copies


def _while_computations(hlo):
    """Instruction lines of every computation a while body runs, fused
    computations included, keyed by computation name."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line == "}":
            name = None
        elif name is not None:
            comps[name].append(line)
    todo = re.findall(r"body=%([\w.\-]+)", hlo)
    seen = {}
    while todo:
        n = todo.pop()
        if n in seen:
            continue
        seen[n] = comps[n]
        for line in comps[n]:
            todo += re.findall(
                r"(?:calls|body|condition|to_apply)=%([\w.\-]+)", line)
    return seen


def test_paged_decode_scan_copies_no_layer_pool(one_chip, monkeypatch):
    """The model's pallas_paged decode step at phi3 widths (8 slots x
    1024 positions in 16-row pages: 513 pages with the trash page) hands
    the kernel the layer-stacked pools, so no slice, write-back or copy
    of one layer's pool runs inside the layer scan."""
    # the backend is the CPU here: compile the kernel, not its interpreter
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    max_len = 1024
    nb = max_len // PAGE
    cfg = dataclasses.replace(get_config("phi3-mini-3.8b"), num_layers=2)
    model = build_model(cfg, max_seq=max_len)

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = shapes(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = shapes(jax.eval_shape(
        lambda: model.init_paged_cache(SLOTS * nb + 1, PAGE)))

    def step(params, cache, tokens, pos, pt):
        return model.decode_step(params, cache, {"tokens": tokens}, pos,
                                 attn_impl="pallas_paged", page_table=pt)

    ints = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
            for s in ((SLOTS, 1), (SLOTS,), (SLOTS, nb))]
    hlo = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, *ints).compile().as_text()
    bodies = _while_computations(hlo)
    assert any("tpu_custom_call" in line
               for lines in bodies.values() for line in lines)
    layer_pool = re.compile(
        rf"= bf16\[(1,)?{SLOTS * nb + 1},{PAGE},{KV_HEADS},{HEAD_DIM}\]"
        r"\S* (dynamic-slice|dynamic-update-slice|copy|copy-done)\(")
    copies = [line.strip()[:120] for lines in bodies.values()
              for line in lines if layer_pool.search(line)]
    assert not copies, copies


@pytest.mark.parametrize("widths", list(WIDTHS))
def test_fused_sample_compiles_at_unaligned_vocab(one_chip, widths):
    slots, vocab = WIDTHS[widths][0], WIDTHS[widths][-1]
    _compile(sampling.fused_sample, one_chip, ((slots, vocab), jnp.float32),
             ((slots,), jnp.float32), ((2,), jnp.uint32))


@pytest.mark.parametrize("cell", list(CELLS))
def test_serve_programs_fit_one_chip(one_chip, monkeypatch, cell):
    """The paged engine's 8-tick decode window and its widest suffix
    prefill (``PREFILL_ROWS`` rows), at the cell's depth, slots and
    positions, each hold their arguments and temporaries in one chip's
    memory; and the compiled KV pool is no more than 1.5 x its logical
    bytes (the (page, kv heads, head width) blocks are what the layout
    may pad)."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    # no pool or parameters are made here: the programs see shapes only
    monkeypatch.setattr(PagedEngine, "reset", lambda self, seed=None: None)
    arch, layers, slots, max_len, widest = CELLS[cell]
    cfg = dataclasses.replace(get_config(arch), num_layers=layers)
    model = build_model(cfg, max_seq=max_len)
    eng = PagedEngine(model, None, slots=slots, max_len=max_len,
                      page_size=PAGE, eos_id=None, ticks_per_sync=8,
                      record_traffic=False, attn_impl="pallas_paged",
                      sample_impl="pallas")
    nb, R = max_len // PAGE, eng.rows

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = shaped(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = shaped(jax.eval_shape(
        lambda: model.init_paged_cache(slots * nb + 1, PAGE)))
    i32, f32 = jnp.int32, jnp.float32
    state = {"last": arr(i32, slots), "pos": arr(i32, slots),
             "active": arr(bool, slots), "remaining": arr(i32, slots),
             "temps": arr(f32, slots)}
    key = arr(jnp.uint32, 2)
    window = eng._window_jit.lower(
        params, cache, state, key, arr(bool, slots),
        arr(i32, slots, nb)).compile()
    prefill = eng._prefill_jit.lower(
        params, cache, state, arr(i32, R, widest), arr(i32, slots, nb),
        *[arr(i32, R)] * 5, arr(f32, R), key,
        arr(bool, R, widest)).compile()
    for prog in (window, prefill):
        ma = prog.memory_analysis()
        assert ma.argument_size_in_bytes + ma.temp_size_in_bytes \
            < CHIP_BYTES
    logical = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    # the window returns the donated pool and the slot state
    assert window.memory_analysis().output_size_in_bytes \
        <= 1.5 * logical + 2 ** 20


def test_cache_sim_ladder_compiles_for_long_traces(one_chip):
    """A million-access trace is four times what SMEM could hold whole."""
    _compile(lambda t: cache_sim.cache_sim_ladder(
        t, (64, 181, 512, 1448, 4096), ways=16), one_chip,
        ((4, 1 << 20), jnp.int32))
