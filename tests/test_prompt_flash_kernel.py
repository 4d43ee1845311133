"""The Pallas prompt kernel (kernels/pallas_attention.prompt_flash) that
`decode_attention.blocked_attention` hands a prompt's attention to on a
TPU: its parity with the `jnp` blocked form in the TPU interpreter, the
predicate that decides where it runs, and the two counts the serving
families put on `serving.prefill` from the same predicate.

TOLERANCE. Both forms walk key blocks with a float32 running softmax over
operands in their own dtype; they differ in the order of summation (one
matmul over a group's rows against an einsum a block, key blocks of the
tile rule's size against 512) by a few float32 ulps of O(1) outputs: 2e-6
in float32. In bf16 the output is rounded once more: 1e-2 of O(1) is two
bf16 ulps, and a dropped query, key or head moves outputs by 0.1 and more
at these sizes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import paddle_tpu.device as device
from paddle_tpu.kernels import decode_attention as da
from paddle_tpu.kernels import pallas_attention as pa
from paddle_tpu.models import cohere2_moe, jamba, joyai_llm_flash
from paddle_tpu.parallel.mesh import use_mesh

TOL = {jnp.float32: 2e-6, jnp.bfloat16: 1e-2}


def _qkv(B, T, H, KV, hd, vd, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, T, H, hd)).astype(dtype),
            jax.random.normal(ks[1], (B, T, KV, hd)).astype(dtype),
            jax.random.normal(ks[2], (B, T, KV, vd)).astype(dtype))


def _diff(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


# name: (T, H, KV, hd, vd, window, chunk, tile, true_len)
CASES = {
    # Command A+'s form: 16 query heads a K/V head, a window shorter than
    # the prompt, the prompt in two chunks of queries (q_offset 0, 256);
    # small tiles, so that every walk (window edge, whole, diagonal) runs
    "g16_window_two_chunks": (512, 32, 2, 128, 128, 200, 256, (32, 128),
                              None),
    # one query head a K/V head, a value width of its own
    "g1_own_value_width": (256, 4, 4, 128, 256, None, 256, (64, 128), None),
    # Jamba's form: 20 query heads on ONE K/V head, the tile rule's tiles
    "g20_one_kv_head": (256, 20, 1, 128, 128, None, 256, None, None),
    # a bucket whose tail past true_len is padding holding garbage
    "padded_tail": (256, 8, 2, 128, 128, 96, 128, (64, 128), 200),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_the_blocked_form(case, dtype):
    T, H, KV, hd, vd, window, chunk, tile, true_len = CASES[case]
    q, k, v = _qkv(1, T, H, KV, hd, vd, dtype)
    if true_len is not None:
        # the rows past true_len hold what a padded bucket may: anything
        tail = jnp.arange(T)[None, :, None, None] >= true_len
        garbage = [jnp.where(tail, x * 1e3 + 7.0, x) for x in (q, k, v)]
    for start in range(0, T, chunk):
        qc = q[:, start:start + chunk]
        want = da._blocked_scan(qc, k, v, window, 512, start)
        got = pa.prompt_flash(qc, k, v, window=window, q_offset=start,
                              tile=tile, interpret=True)
        assert got.shape == want.shape and got.dtype == dtype
        assert _diff(got, want) <= TOL[dtype]
        if true_len is not None:
            dirty = pa.prompt_flash(garbage[0][:, start:start + chunk],
                                    *garbage[1:], window=window,
                                    q_offset=start, tile=tile,
                                    interpret=True)
            rows = max(0, min(chunk, true_len - start))
            # the valid rows never see the tail: bit for bit
            np.testing.assert_array_equal(np.asarray(dirty[:, :rows]),
                                          np.asarray(got[:, :rows]))


def test_blocked_attention_hands_the_call_to_the_kernel(monkeypatch):
    """On a TPU `blocked_attention` runs the kernel (here in the
    interpreter) with its own tiles; the `jnp` blocks otherwise."""
    q, k, v = _qkv(1, 256, 32, 2, 128, 128, jnp.float32)
    calls, kernel = [], pa.prompt_flash

    def counted(*a, **kw):
        calls.append(kw)
        return kernel(*a, **kw, interpret=True)
    monkeypatch.setattr(pa, "prompt_flash", counted)
    plain = da.blocked_attention(q, k, v, window=100)
    assert calls == []
    monkeypatch.setattr(device, "is_tpu", lambda: True)
    got = da.blocked_attention(q[:, 128:], k, v, window=100, q_offset=128)
    assert calls == [{"window": 100, "q_offset": 128}]
    assert _diff(got, plain[:, 128:]) <= TOL[jnp.float32]


def _cell_shapes():
    """(family module, the cell's config as the program's defaults give
    it, prompt buckets of the cell's pool, whether the kernel takes them)."""
    return [
        ("command_a_plus", cohere2_moe, cohere2_moe.Cohere2MoeConfig(
            num_layers=4), [256, 512, 1024, 2048, 4096, 8192, 16384], True),
        ("jamba2", jamba, jamba.JambaConfig(), [128, 256, 1024, 4096, 8192],
         True),
        ("jamba2_short", jamba, jamba.JambaConfig(), [32, 64], False),
        # q/k 192 wide: no whole 128-lane group, the `jnp` form
        ("joyai", joyai_llm_flash, joyai_llm_flash.JoyaiLlmFlashConfig(
            num_layers=13), [1024, 4096, 8192, 16384], False),
    ]


@pytest.mark.parametrize("name,fam,cfg,buckets,takes",
                         _cell_shapes(), ids=[c[0] for c in _cell_shapes()])
def test_the_predicate_at_each_cells_shapes(monkeypatch, name, fam, cfg,
                                            buckets, takes):
    """The families' prompt counts ask the predicate of every prompt
    call's shapes: on a TPU every pair is the kernel's where the shapes
    admit it, none where they do not, and none off a TPU."""
    for bucket in buckets:
        counts = fam.prompt_counts(cfg, bucket)
        assert counts["attn_pairs"] > 0 and counts["attn_pairs_kernel"] == 0
    monkeypatch.setattr(device, "is_tpu", lambda: True)
    for bucket in buckets:
        counts = fam.prompt_counts(cfg, bucket)
        assert counts["attn_pairs_kernel"] == (
            counts["attn_pairs"] if takes else 0), bucket


def test_the_predicate_refuses_a_mesh_and_a_traced_offset(monkeypatch):
    monkeypatch.setattr(device, "is_tpu", lambda: True)
    shape = (2048, 16384, 128, 8, 128, 128, jnp.bfloat16)
    assert da.prompt_kernel_engages(*shape, 14336)
    assert not da.prompt_kernel_engages(*shape, jnp.int32(14336))
    assert not da.prompt_kernel_engages(*shape, 16384)     # past the keys
    assert not da.prompt_kernel_engages(2048, 16384, 128, 8, 128, 128,
                                        jnp.float16, 0)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("tp",))
    with use_mesh(mesh):
        assert not da.prompt_kernel_engages(*shape, 0)
        assert cohere2_moe.prompt_counts(
            cohere2_moe.Cohere2MoeConfig(num_layers=4),
            4096)["attn_pairs_kernel"] == 0


@pytest.mark.parametrize("T,window", [(100, None), (100, 30), (20, 30),
                                      (4096, 4096), (16384, 4096)])
def test_causal_pairs_counts_what_the_mask_admits(T, window):
    i = np.arange(T)
    seen = np.minimum(i + 1, window or T)
    assert da.causal_pairs(0, T, window) == int(seen.sum())
    half = T // 2
    assert da.causal_pairs(0, half, window) + da.causal_pairs(
        half, T, window) == int(seen.sum())


def test_the_tile_rule():
    # Command A+: 16 heads a K/V head x 64 positions = 1024 rows
    assert pa.prompt_tile(2048, 16384, 128, 128, 16) == (64, 1024)
    assert pa.prompt_tile(256, 256, 128, 128, 16) == (64, 256)
    # Jamba: 20 heads on one K/V head x 32 positions = 640 rows
    assert pa.prompt_tile(4096, 4096, 128, 128, 20) == (32, 1024)
    assert pa.prompt_tile(128, 128, 128, 128, 20) == (32, 128)
    assert pa.prompt_tile(64, 64, 128, 128, 20) is None      # no key block
    assert pa.prompt_tile(8192, 8192, 192, 128, 1) is None   # 192 lanes
    # a grid step that would not fit VMEM is cut to smaller q tiles
    bq, bk = pa.prompt_tile(2048, 65536, 128, 128, 16, 4)
    assert bq < 64 and pa.prompt_vmem_bytes(
        65536, 128, 128, 16 * bq, bk, 4) <= pa.PROMPT_VMEM_BUDGET \
        < pa.prompt_vmem_bytes(65536, 128, 128, 32 * bq, bk, 4)


def test_a_cohere2_prompt_through_the_kernel_matches_the_reference(
        monkeypatch):
    """A small Command A+ whose head width the kernel takes, served as on
    a TPU with the kernel in the interpreter: the prompt's logits and the
    decoded ones against the plain reference, and the engine's counts say
    every pair of the prompt was the kernel's."""
    import test_cohere2_moe as t
    from paddle_tpu.inference.router import create_router
    from paddle_tpu.profiler import clear_profiler_spans, get_profiler_spans
    cfg = t.make_cfg(head_dim=128, sliding_window=40, prefill_chunk=64,
                     max_seq_len=256)
    params = t.make_params(cfg)
    monkeypatch.setattr(device, "is_tpu", lambda: True)
    monkeypatch.setattr(pa, "prompt_flash", functools.partial(
        pa.prompt_flash, interpret=True))
    prompt, forced = t._tokens(100, 1), t._tokens(4, 2)
    got, _ = t.serve_logits(cfg, params,
                            cohere2_moe.init_kv_cache(cfg, 2, 256), 1,
                            prompt, forced)
    want = t.reference_rows(cfg, params, prompt, forced)
    np.testing.assert_allclose(got, want, atol=t.TOL, rtol=0)
    router = create_router(params, cfg, replicas=1, family="cohere2_moe",
                           num_slots=2, max_len=256)
    clear_profiler_spans()
    t._serve(router, [prompt], max_new=2)
    prefills = [s.counts for s in get_profiler_spans()
                if s.name == "serving.prefill"]
    assert len(prefills) == 1 and prefills[0]["bucket"] == 128
    assert prefills[0]["attn_pairs_kernel"] == prefills[0]["attn_pairs"] \
        == 3 * da.causal_pairs(0, 128, 40) + da.causal_pairs(0, 128)
    router.close()
