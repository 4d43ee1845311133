"""Sharded checkpoint tests: round-trip, mesh reshape, GPT train state.

Reference analog: auto_parallel Converter tests (merge/slice on parallel-
degree change) run on the virtual 8-device CPU mesh.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.parallel.mesh import build_mesh, use_mesh, shard_value, P
from paddle_tpu.parallel.checkpoint import (save_sharded, load_sharded,
                                            Converter, save_train_state,
                                            load_train_state)


def test_roundtrip_unsharded(tmp_path):
    state = {"a": jnp.arange(12.0).reshape(3, 4),
             "nested": {"b": jnp.ones((5,), jnp.float32)},
             "step": jnp.asarray(7.0),
             "lst": [jnp.zeros((2,)), jnp.full((2,), 3.0)]}
    save_sharded(state, str(tmp_path / "ck"))
    back = load_sharded(str(tmp_path / "ck"), mesh=None)
    np.testing.assert_array_equal(np.asarray(back["a"]),
                                  np.asarray(state["a"]))
    np.testing.assert_array_equal(np.asarray(back["nested"]["b"]),
                                  np.ones(5))
    assert float(back["step"]) == 7.0
    np.testing.assert_array_equal(np.asarray(back["lst"][1]),
                                  np.full((2,), 3.0))


def test_sharded_files_not_full_arrays(tmp_path):
    """Each saved file holds one true shard, not the full array (no host
    ever materializes the global value), and the manifest records the
    PartitionSpec + one window per mesh device."""
    import json
    mesh = build_mesh({"dp": 2, "mp": 4})
    x = jnp.arange(8 * 8, dtype=jnp.float32).reshape(8, 8)
    with use_mesh(mesh):
        xs = shard_value(x, P("dp", "mp"), mesh)
        save_sharded({"w": xs}, str(tmp_path / "ck"))
    files = [f for f in (tmp_path / "ck").iterdir()
             if f.suffix == ".npy"]
    assert len(files) == 8          # nshards == mesh size (2x4)
    for f in files:
        assert np.load(f).shape == (4, 2)          # 8/2 x 8/4
    manifest = json.loads((tmp_path / "ck" / "manifest.json").read_text())
    entry = manifest["leaves"]["w"]
    assert entry["spec"] == ["dp", "mp"]           # spec round-trips
    assert len(entry["shards"]) == 8


def test_raw_jax_array_params_save_true_shards(tmp_path):
    """Regression for the hasattr(leaf, '_value') bug: raw jax.Array state
    (the GPT functional-params path) must save per-device shards with a
    recorded spec — NOT one replicated full-array file with spec []."""
    import json
    mesh = build_mesh({"dp": 2, "mp": 4})
    w = jnp.arange(16 * 4, dtype=jnp.float32).reshape(16, 4)
    with use_mesh(mesh):
        ws = shard_value(w, P("dp", None), mesh)
        assert isinstance(ws, jax.Array)           # raw array, no facade
        save_sharded({"w": ws}, str(tmp_path / "ck"))
    manifest = json.loads((tmp_path / "ck" / "manifest.json").read_text())
    entry = manifest["leaves"]["w"]
    assert entry["spec"] == ["dp", None]
    # dp=2 halves of the array, replicated over mp (replica_id>0 deduped)
    assert len(entry["shards"]) == 2
    windows = sorted(tuple(map(tuple, s["window"])) for s in entry["shards"])
    assert windows == [(((0, 8)), ((0, 4))), (((8, 16)), ((0, 4)))]
    for s in entry["shards"]:
        assert np.load(tmp_path / "ck" / s["file"]).shape == (8, 4)


def test_mesh_reshape_dp2mp4_to_dp4mp2(tmp_path):
    """The acceptance case: save under dp2xmp4, load under
    dp4xmp2, bitwise parity."""
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(8, 8).astype(np.float32))
    b = jnp.asarray(rng.randn(8).astype(np.float32))
    mesh_a = build_mesh({"dp": 2, "mp": 4})
    with use_mesh(mesh_a):
        state = {"w": shard_value(w, P("dp", "mp"), mesh_a),
                 "b": shard_value(b, P("mp"), mesh_a)}
        save_sharded(state, str(tmp_path / "ck"))

    mesh_b = build_mesh({"dp": 4, "mp": 2})
    with use_mesh(mesh_b):
        back = load_sharded(str(tmp_path / "ck"), mesh=mesh_b)
        # shardings follow the recorded specs on the NEW mesh
        assert back["w"].sharding.spec == P("dp", "mp")
        assert dict(back["w"].sharding.mesh.shape) == {"dp": 4, "mp": 2}
    np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(w))
    np.testing.assert_array_equal(np.asarray(back["b"]), np.asarray(b))


def test_reshape_with_spec_override(tmp_path):
    """Converter: load with different target specs (re-slice, e.g. switch
    a weight from row- to column-parallel)."""
    w = jnp.arange(64.0, dtype=jnp.float32).reshape(8, 8)
    mesh_a = build_mesh({"mp": 4})
    with use_mesh(mesh_a):
        save_sharded({"w": shard_value(w, P("mp", None), mesh_a)},
                     str(tmp_path / "ck"))
    mesh_b = build_mesh({"mp": 8})
    back = Converter(str(tmp_path / "ck")).convert(
        mesh_b, specs={"w": P(None, "mp")})
    assert back["w"].sharding.spec == P(None, "mp")
    np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(w))


def test_gpt_train_state_roundtrip_across_meshes(tmp_path):
    """GPT params + AdamW state round-trip dp2xpp2xmp2 -> dp1xpp4xmp2
    with bitwise parity (the 6.7B-on-v5p-64 checkpoint story, in
    miniature)."""
    from paddle_tpu.models.gpt import (GPTConfig, init_gpt_params,
                                       shard_gpt_params, init_opt_state,
                                       PARAM_SPECS)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=4,
                    num_heads=2, ffn_hidden=64, max_seq_len=32,
                    sequence_parallel=False, remat=False,
                    dtype=jnp.float32)
    ref = init_gpt_params(cfg, jax.random.PRNGKey(0))

    mesh_a = build_mesh({"dp": 2, "pp": 2, "mp": 2})
    with use_mesh(mesh_a):
        params = shard_gpt_params(ref, mesh_a)
        opt = init_opt_state(params)
        save_train_state(str(tmp_path / "ck"), params, opt,
                         step=jnp.asarray(3.0))

    mesh_b = build_mesh({"dp": 1, "pp": 4, "mp": 2})
    with use_mesh(mesh_b):
        state = load_train_state(str(tmp_path / "ck"), mesh=mesh_b)
    for k, v in ref.items():
        np.testing.assert_array_equal(
            np.asarray(state["params"][k]), np.asarray(v), err_msg=k)
        np.testing.assert_array_equal(
            np.asarray(state["opt_state"]["m"][k]),
            np.zeros_like(np.asarray(v)), err_msg=k)
    assert float(state["step"]) == 3.0


def test_missing_data_raises(tmp_path):
    from paddle_tpu.testing import faults
    mesh = build_mesh({"mp": 2})
    with use_mesh(mesh):
        save_sharded({"w": shard_value(jnp.ones((4, 4)), P("mp"), mesh)},
                     str(tmp_path / "ck"))
    # delete one shard file -> load must fail loudly, not zero-fill
    # (faults.remove_shard also exempts the dir from the write audit)
    faults.remove_shard(str(tmp_path / "ck"))
    with pytest.raises(ValueError, match="missing data"):
        load_sharded(str(tmp_path / "ck"), mesh=None)
