"""The main path's Pallas kernels compile for the real chip — with no chip.

The TPU compiler is installed next to the CPU backend and compiles for a
chip that is described, not attached (on-chip-measurement guide §2.3).
Interpret-mode tests cannot see what Mosaic refuses (tiling, VMEM
budget); these can, at Llama-3.2-1B and Llama-3-8B attention widths and
at the training cell's share a chip, at half a minute in all. Nothing
runs, so nothing here is a result or a time.
"""
import math
import os
import re
import types

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops.pallas.flash_attention import flash_attention
from ray_tpu.ops.pallas.paged_attention import paged_decode_attention

WIDTHS = {"llama1b": (32, 8, 64), "llama8b": (32, 8, 128)}  # Hq, Hkv, D
SEQ = 2048
SLOTS = 8


@pytest.fixture(scope="module")
def topology():
    """A described v5e:2x2; the persistent compile cache is off around
    the module (an entry written without a chip cannot be read back and
    only warns)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / no such topology
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topology):
    """SingleDeviceSharding on one chip of the described topology."""
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topology.devices[0])


def _flash_fwd(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=False)


def _flash_bwd(q, k, v):
    def loss(q, k, v):
        return _flash_fwd(q, k, v).astype(jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("fn", [_flash_fwd, _flash_bwd],
                         ids=["fwd", "bwd"])
def test_flash_attention_compiles_for_v5e(chip, fn, width):
    hq, hkv, d = WIDTHS[width]
    q = jax.ShapeDtypeStruct((1, SEQ, hq, d), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((1, SEQ, hkv, d), jnp.bfloat16,
                              sharding=chip)
    _assert_mosaic(jax.jit(fn).lower(q, kv, kv).compile())


# (batch, seq, Hq, Hkv, D, dtype): `mistral7b_train_fsdp2_tp2`'s share a
# chip (the tiles the cell runs with), and sequences that are no
# multiple of a tile at both head widths
FLASH_SHAPES = {
    "train_cell": (2, 4096, 16, 4, 128, jnp.bfloat16),
    "ragged_1000x64": (1, 1000, 4, 4, 64, jnp.bfloat16),
    "ragged_300_f32": (1, 300, 4, 2, 128, jnp.float32),
}


@pytest.mark.parametrize("shape", sorted(FLASH_SHAPES))
@pytest.mark.parametrize("fn", [_flash_fwd, _flash_bwd],
                         ids=["fwd", "bwd"])
def test_flash_attention_chosen_tiles_compile_for_v5e(chip, fn, shape):
    """No tile is passed: what compiles is what `choose_blocks` picked,
    under the VMEM limit the kernels ask for, so a tile Mosaic or VMEM
    refuses fails here and not in a chip call."""
    b, s, hq, hkv, d, dtype = FLASH_SHAPES[shape]
    q = jax.ShapeDtypeStruct((b, s, hq, d), dtype, sharding=chip)
    kv = jax.ShapeDtypeStruct((b, s, hkv, d), dtype, sharding=chip)
    text = jax.jit(fn).lower(q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") >= (1 if fn is _flash_fwd else 3)


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("page_size", [16, 64])
def test_paged_decode_compiles_for_v5e(chip, page_size, width):
    hq, hkv, d = WIDTHS[width]
    pages = SEQ // page_size
    n_flat = (SLOTS * pages + 1) * page_size

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def decode(q, k_flat, v_flat, page_table, lengths):
        return paged_decode_attention(q, k_flat, v_flat, page_table,
                                      lengths, page_size, interpret=False)

    _assert_mosaic(jax.jit(decode).lower(
        sds((SLOTS, hq, d), jnp.bfloat16),
        sds((n_flat, hkv, d), jnp.bfloat16),
        sds((n_flat, hkv, d), jnp.bfloat16),
        sds((SLOTS, pages), jnp.int32),
        sds((SLOTS,), jnp.int32)).compile())


# (Hq, Hkv, D, pages of 64 a slot): the full-width decode program of
# each serve configuration (`window_pages` 0: Mistral's `max_seq_len`
# 8 320, OLMoE's 4 096), 64 slots and the scratch row
FULL_WIDTH = {"mistral7b": (32, 8, 128, 130), "olmoe7b": (16, 16, 128, 64)}


@pytest.mark.parametrize("config", sorted(FULL_WIDTH))
def test_paged_decode_full_width_compiles_for_v5e(chip, config):
    """The block `choose_pages_per_block` picks compiles at the widest
    window, and the kernel's view of the pool is the pool: no copy of
    it anywhere in the program (a pool of 49 152 tokens is 96 / 192 MiB
    a layer for K alone)."""
    hq, hkv, d, pages = FULL_WIDTH[config]
    rows, ps = 65, 64
    n_flat = 49152 + ps

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def decode(q, k_flat, v_flat, page_table, lengths):
        return paged_decode_attention(q, k_flat, v_flat, page_table,
                                      lengths, ps, interpret=False)

    compiled = jax.jit(decode).lower(
        sds((rows, hq, d), jnp.bfloat16),
        sds((n_flat, hkv, d), jnp.bfloat16),
        sds((n_flat, hkv, d), jnp.bfloat16),
        sds((rows, pages), jnp.int32),
        sds((rows,), jnp.int32)).compile()
    _assert_mosaic(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("rows", [65, 2048], ids=["decode", "prefill"])
def test_dropless_expert_layer_compiles_for_v5e(chip, rows, monkeypatch):
    """OLMoE's expert layer at published widths (64 experts of 2048 x
    1024, 8 a token): the grouped matmuls are Mosaic kernels (megablox,
    the TPU branch of `grouped_matmul`) at the row counts of a decode
    step and of a prefill group, and at 16 MHA heads of 128 (rep 1) the
    paged kernel compiles too."""
    from ray_tpu.ops.moe import gmm_tiling, moe_dropless, route
    # the code under test asks which backend it runs on; the compile is
    # for the described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    e, d, f, k = 64, 2048, 1024, 8
    # the tiles of before PR 41: the same programs
    assert gmm_tiling(d, f) == gmm_tiling(f, d) == (128, 1024, 1024)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def layer(x, logits, wg, wu, wd, mask):
        weights, idx = route(logits, k, "softmax_topk")
        return moe_dropless(x, weights, idx, wg, wu, wd, mask)

    text = jax.jit(layer).lower(
        sds((rows, d), jnp.bfloat16), sds((rows, e), jnp.float32),
        sds((e, d, f), jnp.bfloat16), sds((e, d, f), jnp.bfloat16),
        sds((e, f, d), jnp.bfloat16), sds((rows,), bool)).compile().as_text()
    assert text.count("tpu_custom_call") >= 3 and "ragged-dot" not in text
    if rows == 65:
        pages, ps = 4096 // 64, 64
        n_flat = (rows * 8 + 1) * ps

        def decode(q, kf, vf, table, lengths):
            return paged_decode_attention(q, kf, vf, table, lengths, ps,
                                          interpret=False)
        _assert_mosaic(jax.jit(decode).lower(
            sds((rows, 16, 128), jnp.bfloat16),
            sds((n_flat, 16, 128), jnp.bfloat16),
            sds((n_flat, 16, 128), jnp.bfloat16),
            sds((rows, pages), jnp.int32),
            sds((rows,), jnp.int32)).compile())


@pytest.mark.parametrize("pages", [16, 64], ids=["window16", "full"])
def test_latent_decode_compiles_for_v5e(chip, pages):
    """sarvam-105b's absorbed decode attention at the cell's shapes (128
    slots and the scratch row, 64 heads over a 640-wide pool row = 512 +
    64 padded to whole lanes, a pool of 262 144 tokens): the block
    `choose_pages_per_block` picks compiles, and the kernel reads the
    pool where it lies (335 MB a layer: no copy anywhere)."""
    from ray_tpu.ops.pallas.latent_attention import latent_decode_attention
    rows, h, w, ps = 129, 64, 640, 64

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def decode(q, flat, table, lengths):
        return latent_decode_attention(q, flat, table, lengths, ps,
                                       d_v=512, scale=0.135,
                                       interpret=False)
    compiled = jax.jit(decode).lower(
        sds((rows, h, w), jnp.bfloat16),
        sds((262144 + ps, w), jnp.bfloat16),
        sds((rows, pages), jnp.int32), sds((rows,), jnp.int32)).compile()
    _assert_mosaic(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def parameter_copies_in_hbm(compiled, at_least=32 * 2 ** 20):
    """The `copy(` / `copy-start(` operations of a compiled program whose
    operand is an entry of the argument named `params` of `at_least`
    bytes or more and whose result stays in HBM (its layout names no
    `S(1)`: a copy into the fast memory ahead of a product is that
    product's one read of the kernel, not a second one)."""
    found = []
    for line in compiled.as_text().splitlines():
        m = re.search(r"= \(?(\w+)\[([\d,]*)\]\{([^}]*)\}.* "
                      r"copy(?:-start)?\(%params", line)
        if m is None or "S(1)" in m.group(3):
            continue
        width = re.search(r"\d+", m.group(1))
        size = math.prod(map(int, m.group(2).split(","))) * (
            int(width.group()) // 8 if width else 1)
        if size >= at_least:
            found.append(line.strip())
    return found


@pytest.mark.parametrize("form", ["decode", "prefill", "prefill_1x1024"])
def test_latent_attention_copies_no_parameter_in_hbm(chip, form,
                                                     monkeypatch):
    """sarvam-105b's attention layer at its published widths, in bf16,
    against the cell's donated pool of 262 144 tokens: a decode step
    (128 slots and the scratch row, 64 pages a row) and a whole prefill
    (2 rows x 2 048 and 1 x 1 024, `fresh`) read every kernel in the
    layout the parameter is held in. Left free to lay `q_proj`'s product
    out by heads of 192, the compiler copied the kernel (4 096 x 12 288,
    101 MB) column-major into an HBM temporary a layer a decode step,
    and in a prefill of one row of 128 or 1 024 (not of 2 x 512 or 2 x
    2 048); the product is pinned flat (models/latent_moe.py:
    LatentAttention)."""
    from ray_tpu.models.latent_moe import LatentAttention, LatentMoEConfig
    from ray_tpu.ops.attention import PagedLatent
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = LatentMoEConfig.sarvam_105b(dtype=jnp.bfloat16,
                                      param_dtype=jnp.bfloat16)
    attn = LatentAttention(cfg)
    rows, s, pages = {"decode": (129, 1, 64), "prefill": (2, 2048, 32),
                      "prefill_1x1024": (1, 1024, 16)}[form]
    ps, pool = 64, 262144

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def call(params, x, cos, sin, flat, table, lengths, positions):
        out, new = attn.apply(
            {"params": params}, x, cos, sin,
            PagedLatent(flat, table, lengths, ps, fresh=form != "decode"),
            positions)
        return out, new.flat
    rope = sds((cfg.max_seq_len, cfg.qk_rope_dim // 2), jnp.float32)
    args = (sds((rows, s, cfg.d_model), jnp.bfloat16), rope, rope,
            sds((pool + ps, cfg.cache_width), jnp.bfloat16),
            sds((rows, pages), jnp.int32), sds((rows,), jnp.int32),
            sds((rows, s), jnp.int32))
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(
            lambda *a: attn.init(jax.random.PRNGKey(0), *a)["params"],
            *args[:3], None))
    compiled = jax.jit(call, donate_argnums=(4,)).lower(
        params, *args).compile()
    assert parameter_copies_in_hbm(compiled) == []
    if form == "decode":
        _assert_mosaic(compiled)
        assert compiled.memory_analysis().temp_size_in_bytes < 32 * 2 ** 20


def relayouts_in_hbm(text: str, elements: set) -> list:
    """The `copy(` operations of a compiled program (a change of layout
    or tiling, paid in HBM traffic both ways) whose result has one of
    the `elements` counts, and the `copy-start(`s of such an array
    between two places in HBM. A `copy-start` into or out of the fast
    memory (`S(1)` on one side) is the array's one read or write."""
    shapes = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\(?\w+\[[\d,]*\]"
                             r"\{[^}]*\})", text, re.M))
    found = []
    for line in text.splitlines():
        m = re.search(r"= \(?(\w+\[([\d,]*)\]\{[^}]*\}).* copy(-start)?"
                      r"\((%[\w.\-]+)", line)
        if m is None or not m.group(2) or math.prod(
                map(int, m.group(2).split(","))) not in elements:
            continue
        if m.group(3) and ("S(1)" in m.group(1)
                           or "S(1)" in shapes.get(m.group(4), "")):
            continue
        found.append(line.strip()[:200])
    return found


def small_tiled(text: str, scope: str, at_least=2 ** 20) -> list:
    """The arrays of `at_least` bytes or more that operations of the
    named scope leave in tiles of fewer than eight rows (`T(1,128)`,
    `T(2,128)`, `T(4,128)`: an axis of 1 to 4 in the second-minor
    place, padded and re-tiled around)."""
    found = []
    for m in re.finditer(
            r"= (\w+?)(\d+)\[([\d,]+)\]\{[^}]*?T\(([124]),128\)[^}]*\} "
            r"[\w\-]+\(.*op_name=\"[^\"]*" + re.escape(scope), text):
        size = math.prod(map(int, m.group(3).split(","))) \
            * int(m.group(2)) // 8
        if size >= at_least:
            found.append(m.group(0)[:120])
    return found


@pytest.mark.parametrize("form", ["decode", "prefill"])
@pytest.mark.parametrize("family", ["solar", "olmo_hybrid", "nemotron"])
def test_delta_rule_layer_re_lays_out_neither_its_tail_nor_its_projection(
        chip, family, form, monkeypatch):
    """One delta-rule layer (and the full layer an engine needs for its
    lengths) at Solar-Open2's and Olmo-Hybrid's published widths through
    the engine's own step functions at the cells' slot counts (193 and
    65 pool rows): a decode step and a 1 024-token prefill group (2 rows
    and 1). With the convolution's tail K - 1 = 3 tokens deep on the
    tiled axis the compiler copied the q | k | v projection's result
    into tiles of two rows and the whole tail pool into tiles of four
    and back, a layer a decode step, and in a prefill call the pool
    both ways and the whole projection batch-minor (PERF.md section 6,
    PR 55). The tail a row a slot: no relayout of either, nothing of
    the `conv` scope in tiles under eight rows, and the prefill's rows
    go into the donated pool in place. Nemotron-3-Super's Mamba-2 layer
    (PR 56) runs the same convolution, with a bias, over the xs | B | C
    columns of ONE fused projection (10 240 of 18 560), at 193 rows."""
    from ray_tpu.models import Hybrid, HybridConfig
    from ray_tpu.serve.llm import LLMEngine, LLMEngineConfig
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sized = dict(n_layers=2, vocab_size=1024, max_seq_len=4096,
                 dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    if family == "solar":
        cfg = HybridConfig.solar_open2_250b(
            layer_types=("full_attention", "kda"), n_experts=8,
            experts_per_token=2, **sized)
        slots, group, scope = 192, 2, "kda.conv/"
    elif family == "nemotron":
        sized.pop("n_layers")
        cfg = HybridConfig.nemotron_3_super_120b("M*", **sized)
        slots, group, scope = 192, 2, "ssm.conv/"
    else:
        cfg = HybridConfig.olmo_hybrid_7b(
            layer_types=("linear_attention", "full_attention"), **sized)
        slots, group, scope = 64, 1, "gdn.conv/"
    model = Hybrid(cfg)

    def abstract(tree):
        return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)
    params = abstract(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]))
    pad = 1024
    eng = LLMEngine(model, params, LLMEngineConfig(
        max_slots=slots, max_seq_len=4096, kv_page_size=64,
        kv_pool_tokens=8192, prefill_buckets=(pad,),
        max_prefill_batch=group))
    try:
        s, p = eng._pages.rows_shape()
        args = (params, abstract(eng._pools), abstract(eng._state))

        def ctl(n):
            return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=chip)
        if form == "decode":
            text = eng._decode_paged_jit.lower(
                *args, ctl(s + 3 * s + s * p),
                window_pages=0).compile().as_text()
        else:
            text = eng._prefill_paged_jit.lower(
                *args, ctl(s + 1 + 4 * group + s * p + group * pad),
                pad_len=pad).compile().as_text()
    finally:
        eng.shutdown()
    taps, width = ((cfg.ssm_conv_kernel, cfg.ssm_conv_width)
                   if family == "nemotron"
                   else (cfg.linear_conv_kernel, cfg.conv_width))
    tail = (taps - 1) * width
    assert f"bf16[{s},{tail}]" in text                  # the pool, a row a slot
    rows = s if form == "decode" else group * pad
    assert relayouts_in_hbm(text, {s * tail, rows * width}) == []
    assert small_tiled(text, scope) == []
    if form == "decode":
        # `causal_conv`'s `held`: with the projection's matmul fused into
        # the sums, what rounds its float32 product to the tail's dtype
        # and so keeps a step's tap 0 the next step's tap 1 to the bit
        # (an identity on the CPU, where no other test can miss it)
        assert re.search(r" reduce-precision\(.*exponent_bits=8, "
                         r"mantissa_bits=7.*op_name=\"[^\"]*"
                         + re.escape(scope), text)
    if form == "prefill":
        # the group's rows go into the donated pool where it lies: two
        # updates of a row each, and at the state-space layer's width
        # (that family alone) one scatter of both
        update = "scatter" if family == "nemotron" else "dynamic-update-slice"
        assert re.search(rf"bf16\[{s},{tail}\]\S* {update}\(", text)


@pytest.mark.parametrize("family,rows", [
    ("sarvam", 129), ("sarvam", 4096), ("lfm2", 128), ("lfm2", 2048),
    ("solar", 129), ("solar", 4096),
], ids=["decode", "prefill", "lfm2_decode", "lfm2_prefill", "solar_decode",
        "solar_prefill"])
def test_expert_share_layer_compiles_for_v5e(chip, family, rows,
                                             monkeypatch):
    """An expert layer that holds 32 of 128 experts (4 096 x 2 048, 8 a
    token, biased sigmoid routing): assignments to the 96 experts held
    elsewhere go to no group, and the grouped matmuls are Mosaic kernels
    at the rows of a decode step and of a prefill group. LFM2-24B-A2B's
    layer holds all 64 of its experts (2 048 x 1 536, 4 a token): its
    tiles divide 1 536 (`gmm_tiling`: one 1 536 wide, 3 MB a buffer of
    the weights' tile) and the compile fits the chip's VMEM; sarvam's
    are the 1 024 x 1 024 they were. Solar-Open2-250B's share holds 40
    of a 320-wide router's experts (4 096 x 1 280, 8 a token): 1 280 is
    ten lane tiles and one whole tile of its side."""
    from ray_tpu.ops.moe import gmm_tiling, moe_dropless, route
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    held, routed, first, d, f, k, scale, up, down = {
        "sarvam": (32, 128, 32, 4096, 2048, 8, 2.5,
                   (1024, 1024), (1024, 1024)),
        "lfm2": (64, 64, 0, 2048, 1536, 4, 1.0,
                 (1024, 1536), (1536, 1024)),
        "solar": (40, 320, 0, 4096, 1280, 8, 1.0,
                  (1024, 1280), (1280, 1024))}[family]
    assert (gmm_tiling(d, f), gmm_tiling(f, d)) == ((128, *up),
                                                    (128, *down))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def layer(x, logits, bias, wg, wu, wd, mask):
        weights, idx = route(logits, k, "sigmoid_bias", True,
                             select_bias=bias, scale=scale)
        return moe_dropless(x, weights, idx, wg, wu, wd, mask,
                            first=first, count=held)

    text = jax.jit(layer).lower(
        sds((rows, d), jnp.bfloat16), sds((rows, routed), jnp.float32),
        sds((routed,), jnp.float32),
        sds((held, d, f), jnp.bfloat16), sds((held, d, f), jnp.bfloat16),
        sds((held, f, d), jnp.bfloat16), sds((rows,), bool)
    ).compile().as_text()
    assert text.count("tpu_custom_call") >= 3 and "ragged-dot" not in text


def test_gdn_decode_step_compiles_for_v5e(chip):
    """Olmo-Hybrid-7B's decode step of the recurrence at the cell's
    shapes (64 slots and the scratch row, 30 heads, keys of 96, values
    of 192): one slot's float32 state (96 x 5 760) a grid step, updated
    in place: the state is aliased, and the program holds no second
    copy of the pool (144 MB a layer)."""
    from ray_tpu.ops.pallas.gdn_decode import gdn_decode_step
    rows, h, dk, dv = 65, 30, 96, 192

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def step(q, k, v, g, beta, state):
        return gdn_decode_step(q, k, v, g, beta, state, interpret=False)
    compiled = jax.jit(step, donate_argnums=(5,)).lower(
        sds((rows, h, dk)), sds((rows, h, dk)), sds((rows, h, dv)),
        sds((rows, h)), sds((rows, h)),
        sds((rows, dk, h * dv))).compile()
    _assert_mosaic(compiled)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= rows * dk * h * dv * 4
    assert mem.temp_size_in_bytes < 2 ** 20


def test_kda_decode_step_compiles_for_v5e(chip):
    """Solar-Open2-250B's decode step of the recurrence with a decay a
    key channel at the cell's shapes (128 slots and the scratch row, 64
    heads, keys and values of 128): one slot's float32 state (128 x
    8 192 = 4 MiB) a grid step, in and out double-buffered inside the
    kernel's 32 MiB of VMEM, updated in place: the state is aliased, and
    the program holds no second copy of the pool (516 MiB a layer)."""
    from ray_tpu.ops.pallas.gdn_decode import kda_decode_step
    rows, h, dk, dv = 129, 64, 128, 128

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def step(q, k, v, g, beta, state):
        return kda_decode_step(q, k, v, g, beta, state, interpret=False)
    compiled = jax.jit(step, donate_argnums=(5,)).lower(
        sds((rows, h, dk)), sds((rows, h, dk)), sds((rows, h, dv)),
        sds((rows, h, dk)), sds((rows, h)),
        sds((rows, dk, h * dv))).compile()
    _assert_mosaic(compiled)
    assert "kda_decode_step" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= rows * dk * h * dv * 4
    assert mem.temp_size_in_bytes < 16 * 2 ** 20


def test_ssm_decode_step_compiles_for_v5e(chip):
    """Nemotron-3-Super's decode step of the state-space recurrence at
    the cell's shapes (192 slots and the scratch row, 128 heads of 64
    over 8 groups of 128 state channels): the no-correction arm of the
    delta rule's kernel under its own name, q and k blocks of 8 columns,
    the decay a third row; one slot's 4 MiB a grid step, aliased."""
    from ray_tpu.ops.pallas.gdn_decode import ssm_decode_step
    rows, h, p, grp, n = 193, 128, 64, 8, 128

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def step(q, k, v, g, beta, state):
        return ssm_decode_step(q, k, v, g, beta, state, interpret=False)
    compiled = jax.jit(step, donate_argnums=(5,)).lower(
        sds((rows, grp, n)), sds((rows, grp, n)), sds((rows, h, p)),
        sds((rows, h)), sds((rows, h)), sds((rows, n, h * p))).compile()
    _assert_mosaic(compiled)
    assert "ssm_decode_step" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= rows * n * h * p * 4
    assert mem.temp_size_in_bytes < 16 * 2 ** 20


def test_kda_chunk_scan_compiles_for_v5e(chip):
    """Solar-Open2-250B's chunkwise form of the recurrence with a decay a
    key channel at the cell's largest prefill group (2 rows x 2 048
    tokens, 64 heads, keys and values of 128): one Mosaic kernel under
    its own name, the rows' state (2 x 4 MiB) updated in place."""
    from ray_tpu.ops.pallas.kda_prefill import kda_chunk_scan
    rows, s, h, dk, dv = 2, 2048, 64, 128, 128

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def scan(q, k, v, g, beta, state, n_new):
        return kda_chunk_scan(q, k, v, g, beta, state, n_new,
                              interpret=False)
    compiled = jax.jit(scan, donate_argnums=(5,)).lower(
        sds((rows, s, h, dk)), sds((rows, s, h, dk)),
        sds((rows, s, h, dv), jnp.bfloat16), sds((rows, s, h, dk)),
        sds((rows, s, h)), sds((rows, dk, h * dv)),
        sds((rows,), jnp.int32)).compile()
    _assert_mosaic(compiled)
    assert "kda_chunk_scan" in compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= rows * dk * h * dv * 4


def _loops_with_a_product(text: str) -> list:
    """The `while` operations of a compiled program whose body, or a
    computation it calls, holds a matrix product."""
    bodies, name = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if m and not line.startswith(" "):
            name = m.group(1)
            bodies[name] = []
        elif name is not None:
            bodies[name].append(line)

    def has_product(comp, seen):
        if comp in seen or comp not in bodies:
            return False
        seen.add(comp)
        for line in bodies[comp]:
            if re.search(r" (convolution|dot)\(", line):
                return True
            if any(has_product(c, seen) for c in re.findall(
                    r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)",
                    line)):
                return True
        return False
    return [m.group(1) for lines in bodies.values() for line in lines
            for m in [re.search(r" while\(.*body=%?([\w.\-]+)", line)]
            if m and has_product(m.group(1), set())]


def arrays_in_hbm(text: str, scope: str, at_least: int) -> list:
    """(type and dimensions, MiB) of the arrays of `at_least` bytes or
    more that the ENTRY computation's operations of the named scope
    define outside the fast memory (no `S(1)`), a tuple's elements
    each: what the scope's results take of the program's HBM heap."""
    entry = text[text.index("\nENTRY "):]
    found = []
    for m in re.finditer(r"^\s*(?:ROOT )?%[\w.\-]+ = (.*?) ([\w\-]+)\(.*"
                         r"op_name=\"[^\"]*" + re.escape(scope),
                         entry[:entry.index("\n}")], re.M):
        if m.group(2) in ("bitcast", "get-tuple-element", "parameter"):
            continue                    # another operation's array
        for kind, bits, dims, layout in re.findall(
                r"([a-z]+)(\d+)\[([\d,]+)\]\{([^}]*)\}", m.group(1)):
            size = math.prod(map(int, dims.split(","))) * int(bits) // 8
            if size >= at_least and "S(1)" not in layout:
                found.append((f"{kind}{bits}[{dims}]", size / 2 ** 20))
    return found


# temp_size_in_bytes of the same programs at the parent commit (PR 52's,
# the scan a `lax.scan` of 51 fusions): AOT, sandbox, this test's engine
SOLAR_PREFILL_TEMP_MIB_BEFORE = {(1024, 1): 214.3, (2048, 2): 1043.2}
# and of the 1 024 x 1 program as it stands, measured (PR 55)
SOLAR_PREFILL_1024_1_TEMP_MIB = 261.2


@pytest.mark.parametrize("pad_len,group",
                         sorted(SOLAR_PREFILL_TEMP_MIB_BEFORE))
def test_solar_prefill_programs_hold_the_chunk_kernel(chip, monkeypatch,
                                                      pad_len, group):
    """`solar250b_decode_sat`'s own prefill programs (the cell's file,
    the engine's jit, four slots for the pools' sake) compile for a
    described v5e with the chunk kernel in each delta-rule layer and no
    loop left that multiplies matrices (the grouped matmul's searches
    stay). The largest program's temporaries are under the parent's.

    The 1 024 x 1 program's are pinned where they stand, with what
    they hold. PR 53 left 238.0 MiB against PR 52's 214.3 (its heap
    packs worse; its peak of live bytes is lower). PR 55 (the
    convolution's tail a row a slot) leaves 261.2: by the compiler's
    buffer assignment (`--xla_dump_to`) the heap in HBM went 225.95 ->
    249.55 MiB, beside 128 MiB of fast memory in both. The three
    `bf16[1027,24576]` arrays in HBM (48 MiB each: the projection with
    its tail in front) are gone: the projection's and the convolution's
    results both lie in the fast memory now, and with them there one of
    the chunk kernel's four 32 MiB float32 inputs a layer (q and k, each
    in two layouts) found no room and is written to HBM and fetched
    back in quarters. HBM holds 9 such arrays where it held 6 and the 3
    of 48 MiB (288 MiB written a call against 336); but the 48 MiB ones
    were dead where the heap peaks, at the scan, and one array of 32 MiB
    more is alive there: the heap's 23.6 MiB. Whoever moves this number
    says which array moved."""
    from benchmarks.harness import modelcfg, replica_solar
    from ray_tpu import models
    from ray_tpu.serve.llm.engine import LLMEngine, LLMEngineConfig
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = modelcfg.load(os.path.join(
        os.path.dirname(__file__), "..", "benchmarks", "configs",
        "solar-open2-250b-serve-ep8-l4.json"), False)
    model = models.Hybrid(replica_solar.hybrid_config(
        cfg, param_dtype=jnp.bfloat16))

    def abstract(tree):
        return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)
    params = abstract(jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0)))
    ecfg = {**cfg["engine"], "max_slots": 4, "kv_pool_tokens": 4 * 4096,
            "prefill_buckets": tuple(cfg["engine"]["prefill_buckets"])}
    eng = LLMEngine(model, params, LLMEngineConfig(**ecfg))
    try:
        s, p = eng._pages.rows_shape()
        compiled = eng._prefill_paged_jit.lower(
            params, abstract(eng._pools), abstract(eng._state),
            jax.ShapeDtypeStruct(
                (s + 1 + 4 * group + s * p + group * pad_len,), jnp.int32,
                sharding=chip), pad_len=pad_len).compile()
    finally:
        eng.shutdown()
    text = compiled.as_text()
    layers = sum(k == "kda" for k in model.cfg.layer_types)
    assert layers == 3
    assert len(re.findall(r"custom-call\(.*kda_chunk_scan", text)) == layers
    assert _loops_with_a_product(text) == []
    temp_mib = compiled.memory_analysis().temp_size_in_bytes / 2 ** 20
    if (pad_len, group) == (2048, 2):
        assert temp_mib < SOLAR_PREFILL_TEMP_MIB_BEFORE[pad_len, group]
        return
    assert temp_mib <= SOLAR_PREFILL_1024_1_TEMP_MIB + 1, temp_mib
    least = 32 * 2 ** 20
    assert arrays_in_hbm(text, "kda.proj/qkv_proj/", least) == []
    held = arrays_in_hbm(text, "kda.conv/", least)
    assert sorted(held) == sorted(layers * [
        ("f32[1,128,64,8,128]", 32.0), ("f32[128,8,64,128]", 32.0),
        ("f32[128,8,64,128]", 32.0)]), held


def test_paged_decode_over_a_pool_laid_out_for_32_heads_compiles(chip):
    """Olmo-Hybrid-7B's full layers: 30 KV heads fill no whole 8-row
    tile, so the pool is declared for 32 (models/hybrid.py:
    kv_pool_heads) and the kernel's view of it is the pool: no copy of
    its 672 MB a layer anywhere in the program."""
    rows, ps, pages, heads = 65, 64, 64, 32
    n_flat = 81920 + ps

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def decode(q, k_flat, v_flat, page_table, lengths):
        return paged_decode_attention(q, k_flat, v_flat, page_table,
                                      lengths, ps, interpret=False)
    compiled = jax.jit(decode).lower(
        sds((rows, heads, 128), jnp.bfloat16),
        sds((n_flat, heads, 128), jnp.bfloat16),
        sds((n_flat, heads, 128), jnp.bfloat16),
        sds((rows, pages), jnp.int32), sds((rows,), jnp.int32)).compile()
    _assert_mosaic(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("width", ["lfm2", "llama1b"])
def test_a_packed_pool_of_narrow_heads_is_held_and_read_as_it_is(
        chip, width, monkeypatch):
    """Heads of 64 packed two to a 128-lane row
    (ops/attention.py:packed_kv_shape): a decode step's write into the
    pool and the paged kernel over it, at LFM2-24B-A2B's full layers
    (32 query over 8 KV heads, 129 rows, a pool of 262 144 tokens) and
    at Llama-3.2-1B's heads. The pool takes its counted bytes in HBM
    (256 MiB an array at 4 rows of 128 lanes a token: no padding to
    lanes or to 8-row tiles), is updated in place, and the kernel's
    view of it is the pool: no temporary of its size."""
    from ray_tpu.ops.attention import (PagedKV, packed_kv_shape,
                                       paged_cached_attention)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hq, hkv, d = {"lfm2": (32, 8, 64), "llama1b": WIDTHS["llama1b"]}[width]
    rows, ps, pages = 129, 64, 64
    n_flat = 262144 + ps
    row = packed_kv_shape(hkv, d)
    assert row == (4, 128)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def step(q, k, v, k_flat, v_flat, table, lengths):
        out, new = paged_cached_attention(
            q, k, v, PagedKV(k_flat, v_flat, table, lengths, ps),
            lengths[:, None])
        return out, new.k_flat, new.v_flat
    pool = sds((n_flat, *row), jnp.bfloat16)
    compiled = jax.jit(step, donate_argnums=(3, 4)).lower(
        sds((rows, 1, hq, d), jnp.bfloat16),
        sds((rows, 1, hkv, d), jnp.bfloat16),
        sds((rows, 1, hkv, d), jnp.bfloat16), pool, pool,
        sds((rows, pages), jnp.int32), sds((rows,), jnp.int32)).compile()
    _assert_mosaic(compiled)
    mem = compiled.memory_analysis()
    counted = 2 * n_flat * 4 * 128 * 2
    assert counted <= mem.argument_size_in_bytes < counted + 4 * 2 ** 20
    assert mem.alias_size_in_bytes >= counted
    assert mem.temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("policy,kept,rings,flash_calls,temp_gib", [
    ("attention", 6, 10, 3, 1.30), ("full", 0, 11, 4, 1.36)])
def test_train_step_hides_its_tp_sums_on_fsdp2_tp2(
        topology, monkeypatch, policy, kept, rings, flash_calls, temp_gib):
    """ONE layer of the training cell's step (published Mistral-7B
    widths, `MeshSpec(fsdp=2, tp=2)`, 4 x 4 096 tokens, float32
    parameters, remat) compiled for the four described chips: the
    blocks' tensor-parallel sums are rings of asynchronous
    collective-permutes beside their matmuls
    (parallel/collective_matmul.py), no all-reduce of a block's
    activations over `tp` is left, the optimizer reads every kernel's
    gradient in the parameter's own layout, and the temporaries stay
    near what they were with the all-reduces (1.32 GiB under "full";
    1.27 at the parent of PR 44 for the same shape, 1.62 before the
    MLP's chunks went unassembled). Keeps a later change from bringing
    the exposed sum back. Under the default policy (PR 45) the backward
    keeps the attention half's residuals: the flash forward is in the
    program once, o_proj's ring is not rerun, and the temporaries read
    1.26 GiB (the peak is at the end of the backward, where the kept
    values are gone)."""
    from ray_tpu.models import Llama, LlamaConfig
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.train.optim import make_optimizer, warmup_cosine
    from ray_tpu.train.spmd import make_train_step
    # "auto" attention asks which backend it runs on; the compile is for
    # the described chips, where it is the flash kernel
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seq, batch = 4096, 4
    cfg = LlamaConfig(vocab_size=32768, d_model=4096, n_layers=1,
                      n_heads=32, n_kv_heads=8, d_ff=14336, max_seq_len=seq,
                      rope_theta=1e6, remat=True, remat_policy=policy,
                      param_dtype=jnp.float32)
    mesh = build_mesh(MeshSpec(fsdp=2, tp=2), devices=topology.devices)
    tx = make_optimizer("adamw", schedule=warmup_cosine(3e-4, 100, 10 ** 5),
                        grad_clip=1.0)
    init_fn = make_train_step(Llama(cfg), tx, mesh)
    made = {}

    def init(rng):      # nothing can be placed on a described chip
        state, made["step"] = init_fn(
            rng, {"tokens": jnp.zeros((batch, seq + 1), jnp.int32)})
        return state

    state = jax.eval_shape(init, jax.random.PRNGKey(0))
    step = made["step"]
    state = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        state, step.state_shardings)
    tokens = jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32,
                                  sharding=step.batch_shardings["tokens"])
    compiled = step.step_fn.lower(state, {"tokens": tokens}).compile()
    assert step.tp_overlapped_matmuls == 7
    assert step.remat_saved_residuals == kept
    text = compiled.as_text()
    # the embedding's lookup sums bf16[4,S,4096] over all four chips and
    # stays (ISSUE 44, out of scope); a block's sum was bf16[2,S,4096]
    # over the `tp` pairs
    block_sums = re.findall(
        r"= bf16\[2,%d,4096\]\S* all-reduce\(" % seq, text)
    assert not block_sums, block_sums
    starts = len(re.findall(r" collective-permute-start\(", text))
    dones = len(re.findall(r" collective-permute-done\(", text))
    assert starts == dones, (starts, dones)
    # a ring at `tp` 2 is one permute of half the rows: forward 4,
    # backward 4, and the rematted forward's 3 under "full" (the gathers
    # in front of q / k / v and of gate / up, o_proj's scatter; down_proj's
    # feeds nothing the backward reads). With the attention half kept
    # o_proj's goes; the gather in front of q / k / v stays without its
    # matmuls, because the half it delivers is what q / k / v's kernels'
    # gradients multiply and is not kept (ROADMAP A7 (c))
    ring = r"= \(bf16\[2,%d,4096\][^=]* collective-permute-start\(" % (
        seq // 2)
    assert len(re.findall(ring, text)) == rings
    # the flash kernels: forward, dQ, dK/dV, and the forward again where
    # the backward reruns it
    assert len(re.findall(r'custom_call_target="tpu_custom_call"',
                          text)) == flash_calls
    # a kernel's gradient formed the other way round makes the optimizer
    # transpose the parameter and both moments (collective_matmul._dot)
    relaid = re.findall(r"= f32\[\d+,\d+\]\{0,1\S* copy\(", text)
    assert not relaid, relaid
    assert "tpu_custom_call" in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= temp_gib * 2 ** 30, temp / 2 ** 30


def test_xing_decode_step_fuses_the_residual_path_and_copies_nothing(
        chip, monkeypatch):
    """The benchmark's Xing4.0-29B-A4B cut (1 dense + 5 expert layers at
    the published widths, bf16) in a decode step of 128 slots and the
    scratch row against donated pools of 262 144 tokens: each of the 12
    sub-layers' residual path is exactly two Mosaic kernels (`hc_mix_in`,
    `hc_mix_out`), 24 in the program, beside the latent kernel of every
    layer and the grouped matmuls; no parameter of 32 MB or more is
    copied in HBM (the low-rank query's `q_b_proj` product is pinned
    flat as `q_proj`'s is) and no pool is (1.9 GiB of them, updated in
    place)."""
    from ray_tpu.models import LatentMoE, LatentMoEConfig
    from ray_tpu.ops.attention import PagedLatent
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = LatentMoEConfig.xing4_29b_a4b(
        n_layers=6, first_dense=1, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    model = LatentMoE(cfg)
    rows, pages, ps, pool = 129, 64, 64, 262144

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def step(params, pools, tokens, table, lengths, mask):
        entries = [PagedLatent(flat, table, lengths, ps) for flat in pools]
        (logits, new), sown = model.apply(
            {"params": params}, tokens, cache=entries,
            positions=lengths[:, None], row_mask=mask,
            mutable=["step_stats"])
        counted = sum(jax.tree_util.tree_leaves(sown["step_stats"]))
        return logits.argmax(-1), counted, [e.flat for e in new]
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))["params"]))
    pools = [sds((pool + ps, cfg.cache_width), jnp.bfloat16)] * cfg.n_layers
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pools, sds((rows, 1), jnp.int32),
        sds((rows, pages), jnp.int32), sds((rows,), jnp.int32),
        sds((rows, 1), jnp.bool_)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    named = {k: sum(f"/{k}" in line or f'"{k}"' in line for line in calls)
             for k in ("hc_mix_in", "hc_mix_out", "latent_decode_attention")}
    assert named == {"hc_mix_in": 12, "hc_mix_out": 12,
                     "latent_decode_attention": 6}, named
    assert parameter_copies_in_hbm(compiled) == []
    pool_copies = [line.strip() for line in text.splitlines()
                   if re.search(r"= bf16\[%d,%d\]\S* copy(-start)?\("
                                % (pool + ps, cfg.cache_width), line)]
    assert pool_copies == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 6 * (pool + ps) * cfg.cache_width * 2
    assert mem.temp_size_in_bytes < 0.6 * 2 ** 30


def hlo_computations(text):
    """A compiled program's text cut into its computations: name ->
    (signature line, instruction lines); the entry's name is "ENTRY"."""
    found, name = {}, None
    for line in text.splitlines():
        m = re.match(r"(ENTRY )?%(\S+) \(.*\) -> .* \{$", line)
        if m:
            name = "ENTRY" if m.group(1) else m.group(2)
            found[name] = (line, [])
        elif line.startswith("}"):
            name = None
        elif name is not None:
            found[name][1].append(line.strip())
    return found


def test_sampler_makes_one_pass_when_all_rows_are_greedy(chip):
    """The engine's sampler alone at the Xing4.0 cell's decode shape
    (128 slots and the scratch row x 131 072 float32 logits): the entry
    computation holds the greedy arg-max, the one fusion that reads the
    logits, and one `conditional`; the draw, its divide and its noise
    stand under it, so a step whose rows are all greedy makes one pass.
    A step with sampled rows and no top_p makes one more, one fusion
    that divides, adds the noise and reduces: the scaled logits are
    never written (only the nucleus branch, which sorts them, does)."""
    from ray_tpu.serve.llm import LLMEngine
    n, v = 129, 131072
    stub = types.SimpleNamespace(
        _jnp=jnp, _jax=jax, cfg=types.SimpleNamespace(logprobs=False,
                                                       top_k=0))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    compiled = jax.jit(
        lambda *a: LLMEngine._sample_tokens(stub, *a)).lower(
            sds((n, v), jnp.float32), sds((n,), jnp.float32),
            sds((n,), jnp.float32), sds((2,), jnp.uint32)).compile()
    comps = hlo_computations(compiled.as_text())
    size = f"[{n},{v}]"

    def calls(lines, op):
        return [line for line in lines if re.search(rf" {op}\(", line)]

    def wide_fusions(lines):
        """The signatures of the fused computations of `lines` that read
        or write an N x V array."""
        called = (comps[re.search(r"calls=%([\w.\-]+)", line).group(1)][0]
                  for line in calls(lines, "fusion"))
        return [signature for signature in called if size in signature]

    def branches(line):
        return [comps[name][1] for name in re.search(
            r"branch_computations=\{([^}]*)\}",
            line).group(1).replace("%", "").split(", ")]
    entry = comps["ENTRY"][1]
    assert len(wide_fusions(entry)) == 1, wide_fusions(entry)
    outer, = calls(entry, "conditional")
    greedy, drawn = branches(outer)     # index 0 is the false branch
    assert wide_fusions(greedy) == []
    assert wide_fusions(drawn) == []    # the divide is not up here
    inner, = calls(drawn, "conditional")
    plain, nucleus = branches(inner)
    assert calls(nucleus, "sort") and not calls(plain, "sort")
    fused, = wide_fusions(plain)
    assert size not in fused.split(" -> ")[1], fused
    results = (re.match(r"(?:ROOT )?%\S+ = (\(.*?\)|\S+) ([\w\-]+)\(", line)
               for line in plain)
    written = [m.group(0) for m in results
               if m and "f32" + size in m.group(1)
               and m.group(2) not in ("parameter", "get-tuple-element")]
    assert written == [], written


def _xing_engine_model():
    from ray_tpu.models import LatentMoE, LatentMoEConfig
    return LatentMoE(LatentMoEConfig.xing4_29b_a4b(
        n_layers=2, first_dense=1, max_seq_len=4096, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)), dict(max_slots=128, max_prefill_batch=2)


def _hybrid_engine_model():
    from ray_tpu.models import Hybrid, HybridConfig
    return Hybrid(HybridConfig.olmo_hybrid_7b(
        n_layers=2, layer_types=("linear_attention", "full_attention"),
        max_seq_len=4096, param_dtype=jnp.bfloat16)), dict(
            max_slots=64, max_prefill_batch=1)


@pytest.mark.parametrize("family", [_xing_engine_model,
                                    _hybrid_engine_model],
                         ids=["xing", "olmo_hybrid"])
def test_prefill_step_multiplies_the_sampled_rows_by_the_head(
        chip, family, monkeypatch):
    """The engine's own `_prefill_paged_step` at the 1 024-token bucket,
    one prompt, over two layers at the published widths of the Xing4.0
    and OLMo-hybrid cells (131 072 and 100 352 words). The timed program
    holds no float32 array of pad_len x vocabulary elements and nothing
    of the model's own `lm_head` product, and its temporaries are
    smaller than those logits alone. The same step traced with the
    benchmark's kind of spy on `_apply_counted` (its first result one
    more output: benchmarks/harness/replica_olmohybrid.py) holds both:
    the checks' taps still read every position's logits."""
    from ray_tpu.serve.llm import LLMEngine, LLMEngineConfig
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, sized = family()
    pad, g, vocab = 1024, 1, model.cfg.vocab_size

    def abstract(tree):
        return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)
    params = abstract(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]))
    eng = LLMEngine(model, params, LLMEngineConfig(
        max_seq_len=4096, kv_page_size=64, kv_pool_tokens=8192,
        prefill_buckets=(pad,), **sized))

    def tapped(params, pools, state, ctl, **kw):
        seen, inner = [], eng._apply_counted

        def spy(*args):
            out = inner(*args)
            seen.append(out[0])
            return out
        eng._apply_counted = spy
        try:
            out = eng._prefill_paged_step(params, pools, state, ctl, **kw)
        finally:
            del eng._apply_counted
        return out, seen[0]

    def wide(text):
        """The float32 results of pad_len x vocabulary elements."""
        found = re.findall(r"= f32\[([\d,]+)\]\S* ([\w\-]+)\(", text)
        return [(dims, op) for dims, op in found
                if math.prod(map(int, dims.split(","))) == g * pad * vocab]
    try:
        s, p = eng._pages.rows_shape()
        args = (params, abstract(eng._pools), abstract(eng._state),
                jax.ShapeDtypeStruct((s + 1 + 4 * g + s * p + g * pad,),
                                     jnp.int32, sharding=chip))
        timed = eng._prefill_paged_jit.lower(*args, pad_len=pad).compile()
        text = timed.as_text()
        assert wide(text) == []
        assert "lm_head/bsd,dv->bsv" not in text
        # the head that is left: the gathered row by the kernel
        assert re.search(
            r'op_name="jit\(\w+\)/\w+\.head/bsd,dv->bsv/dot_general"', text)
        assert timed.memory_analysis().temp_size_in_bytes \
            < g * pad * vocab * 4
        text = jax.jit(tapped, static_argnames=("pad_len",),
                       donate_argnums=(1, 2)).lower(
            *args, pad_len=pad).compile().as_text()
        assert wide(text)
        assert "lm_head/bsd,dv->bsv" in text

        # `_settled` is one barrier of the program as lowered; it goes
        # when nothing taps the logits (PERF.md section 7, PR 50 (a))
        def barriers():      # a new function each time: traced anew
            return jax.jit(lambda *a: eng._prefill_paged_step(
                *a, pad_len=pad)).lower(*args).as_text().count(
                    "optimization_barrier")
        with_it = barriers()
        monkeypatch.setattr(eng, "_settled", lambda entries, h: (entries, h))
        assert with_it == barriers() + 1
    finally:
        eng.shutdown()
