"""chip_smoke.py off the chip: it refuses to run, and its phase functions
are right at toy sizes.

The script has no CPU branch, so ``main()`` must fail here.  Its phase
functions take their sizes as an argument; called with toy sizes on the
virtual CPU mesh (the gossip kernel in interpret mode) they exercise the
same entry points, artifact parsing and checks the chip run relies on.
Also here: the compile-cache helper every entry point calls, and the LM's
attention auto rule, which may no longer hide a kernel the chip refuses.
"""

import importlib.util
import inspect
import json
import logging
import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from stochastic_gradient_push_tpu.ops.gossip_kernel import (
    KernelBackendError, KernelLane)
from stochastic_gradient_push_tpu.parallel import (
    GOSSIP_AXIS, make_gossip_mesh)
from stochastic_gradient_push_tpu.run import gossip_lm
from stochastic_gradient_push_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOG = logging.getLogger("test_chip_smoke")

TOY_IMAGE = {"model": "tiny_cnn", "image_size": 16, "num_classes": 10,
             "batch_size": 4, "precision": "bf16", "epochs": 2,
             "iters_per_epoch": 2}
TOY_W4 = dict(TOY_IMAGE, world_size=4, health_every=2,
              gossip_kernel=KernelLane(interpret=True),
              payload_elems=5000)
TOY_LM = {"d_model": 32, "n_layers": 2, "n_heads": 4, "d_ff": 64,
          "seq_len": 32, "batch_size": 2, "vocab_size": 64,
          "precision": "bf16", "world_size": 1, "num_steps": 4,
          # off the chip the auto rule's answer is full attention and
          # there is no Mosaic kernel to look for
          "attn": "full", "kernel_shape": None}


@pytest.fixture
def smoke(tmp_path, monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke_under_test", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "OUT_DIR", str(tmp_path / "runs"))
    return mod


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    """Phase functions only report on the cache directory; keep the
    entry points' own placement away from the checkout's cache too."""
    path = str(tmp_path / "jax_cache")
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, path)
    return path


# -- the script refuses to run off the chip ---------------------------------


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one_chip", "four_chips"])
def test_main_fails_without_a_tpu(smoke, cache_dir, capsys, argv):
    assert smoke.main(argv) != 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "TPU" in last["reason"]


def test_size_table_is_the_issue_s_configuration(smoke):
    """Full width: nobody shrinks the image, the classes or the model to
    make the smoke pass."""
    r = smoke.SIZES["resnet50_sgp"]
    assert (r["model"], r["image_size"], r["num_classes"],
            r["batch_size"], r["precision"]) == (
        "resnet50", 224, 1000, 128, "bf16")
    lm = smoke.SIZES["lm_dense_flash"]
    assert (lm["d_model"], lm["n_layers"], lm["n_heads"], lm["seq_len"],
            lm["batch_size"], lm["vocab_size"]) == (
        768, 12, 12, 1024, 8, 32768)
    assert lm["attn"] == "flash" and lm["kernel_shape"] == (8, 12, 1024, 64)
    # the lfm2 cell's expert layer: 2 x 4096 tokens x 4 choices, 16 held
    gk = smoke.SIZES["grouped_kernels"]
    assert (gk["rows"], gk["width"], gk["out"], gk["experts"], gk["dtype"],
            gk["interpret"]) == (32768, 2048, 2 * 1792, 16, "bfloat16", False)
    w4 = smoke.SIZES["w4"]
    assert w4["world_size"] == 4 and w4["gossip_kernel"] == "pallas"
    assert {k: w4[k] for k in smoke.RESNET} == smoke.RESNET


# -- phase functions at toy sizes -------------------------------------------


def test_one_chip_image_phase_at_toy_size(smoke, cache_dir):
    line = smoke.resnet50_sgp(dict(TOY_IMAGE, world_size=1), cache_dir)
    assert line["ok"] and line["steps"] == 4
    assert np.isfinite([line["first_loss"], line["last_loss"]]).all()
    assert "not a benchmark" in line["note"]
    assert line["compile_cache_dir"] == cache_dir


def test_one_chip_lm_phase_at_toy_size(smoke, cache_dir):
    line = smoke.lm_dense_flash(TOY_LM, cache_dir)
    assert line["ok"] and line["steps"] == 4 and line["attn"] == "full"
    # the size table's own expectation cannot be met off the chip: the
    # phase must say so, not pass
    with pytest.raises(RuntimeError, match="resolved attention to 'full'"):
        smoke.lm_dense_flash(dict(TOY_LM, attn="flash"), cache_dir)


TOY_GROUPED = {"rows": 2048, "width": 128, "out": 256, "experts": 6,
               "dtype": "float32", "interpret": True, "tolerance": 1e-5}


def test_grouped_kernels_phase_at_toy_size(smoke, cache_dir):
    """Interpreted here; the splits are the chip's, at four tiles."""
    splits = smoke.grouped_splits(2048, 6, 512)
    assert all(s.sum() <= 2048 and len(s) == 6 for s in splits.values())
    assert splits["every_row"].sum() == 2048
    boundary = splits["trailing_empty_on_a_boundary"]
    assert boundary.sum() % 512 == 0 and 0 < boundary.sum() < 2048 \
        and boundary[-1] == 0
    with jax.default_matmul_precision("highest"):
        line = smoke.grouped_kernels(TOY_GROUPED, cache_dir)
    assert line["ok"] and set(line["worst_share_of_largest"]) == {
        "out", "d_rows", "d_blocks"}
    # compiled, the rule has to take the kernels: off the chip it does not
    with pytest.raises(RuntimeError, match="kernel_fits refuses"):
        smoke.grouped_kernels(dict(TOY_GROUPED, interpret=False), cache_dir)


def test_grouped_kernels_phase_fails_on_a_wrong_product(smoke, cache_dir,
                                                        monkeypatch):
    from stochastic_gradient_push_tpu.ops import grouped_matmul as gm

    real = gm._tgmm
    monkeypatch.setattr(gm, "_tgmm", lambda *a, **k: real(*a, **k) * 1.01)
    with jax.default_matmul_precision("highest"), \
            pytest.raises(RuntimeError, match="d_blocks over the split"):
        smoke.grouped_kernels(TOY_GROUPED, cache_dir)


def test_flash_kernel_check_fails_off_the_chip(smoke):
    """Here ``flash_attention`` routes to the blockwise reference: the
    very substitution the check exists to catch."""
    with pytest.raises(RuntimeError, match="no tpu_custom_call"):
        smoke.kernel_in_program((1, 2, 128, 16))


def test_four_chip_phases_at_toy_size(smoke, cache_dir):
    """SGP, AR and the Pallas lane (interpreted) over four virtual
    devices, in the order the script runs them."""
    where = smoke.placement_w4(TOY_W4, cache_dir)
    assert where["devices"] == {"params": [0, 1, 2, 3],
                                "ps_weight": [0, 1, 2, 3]}
    assert where["collective_permutes"] > 0

    sgp = smoke.sgp_w4(TOY_W4, cache_dir)
    assert sgp["steps"] == 4 and sgp["health_events"] >= 2
    assert sgp["ps_mass_err"] <= 1e-6
    assert sgp["ps_w_min"] == sgp["ps_w_max"] == 1.0

    ar = smoke.ar_w4(TOY_W4, cache_dir)
    assert ar["steps"] == 4 and np.isfinite(ar["last_loss"])

    pallas = smoke.sgp_w4_pallas(TOY_W4, cache_dir)
    assert pallas["ok"] and pallas["health_events"] >= 2
    # the interpreted kernel lane is bit-aligned with the XLA lane
    assert pallas["bit_equal"] and pallas["param_scale"] > 0
    # one round, no training in between: exact on the f32 wire, within
    # one rounding on int8 (XLA fuses the dequantize into the axpy)
    round_diff = pallas["round_max_abs_diff_vs_xla"]
    assert round_diff["f32"] == 0.0 and round_diff["int8"] <= 1e-6


def test_refused_pallas_lane_fails_its_phase(smoke, cache_dir):
    """Off the chip ``--gossip_kernel pallas`` is a typed refusal, and a
    refused lane fails the phase in the program's own words."""
    with pytest.raises(KernelBackendError, match="needs a TPU backend"):
        smoke.sgp_w4_pallas(dict(TOY_W4, gossip_kernel="pallas"),
                            cache_dir)


def _stacked_state(put):
    world = 4
    return types.SimpleNamespace(
        params={"w": put(np.ones((world, 8, 8), np.float32))},
        gossip=types.SimpleNamespace(
            ps_weight=put(np.ones((world,), np.float32))))


def test_placement_check_passes_on_four_devices(smoke):
    sharding = NamedSharding(make_gossip_mesh(4), P(GOSSIP_AXIS))
    where = smoke.check_placement(
        _stacked_state(lambda a: jax.device_put(a, sharding)), 4)
    assert where["devices"]["ps_weight"] == [0, 1, 2, 3]


def test_placement_check_fails_for_a_state_on_one_device(smoke):
    one = jax.devices()[0]
    with pytest.raises(RuntimeError, match="lives on 1 device"):
        smoke.check_placement(
            _stacked_state(lambda a: jax.device_put(a, one)), 4)


# -- the compile cache helper -----------------------------------------------


@pytest.fixture
def restore_cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_env_set_leaves_the_config_alone(monkeypatch, tmp_path,
                                               restore_cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path))
    assert compile_cache.place_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_env_unset_is_the_fixed_checkout_path(monkeypatch,
                                                    restore_cache_config):
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.place_compile_cache() == want
    assert compile_cache.place_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_cache_path_is_the_same_in_another_process(monkeypatch, tmp_path):
    """No pid, time or temp dir in the path: a second process — started
    from another directory — finds the first one's entries."""
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.CACHE_DIR_ENV}
    env["PYTHONPATH"] = REPO
    code = ("from stochastic_gradient_push_tpu.utils.compile_cache import "
            "place_compile_cache as p; import jax; "
            "print(p()); print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         cwd=str(tmp_path), capture_output=True, text=True,
                         timeout=120, check=True).stdout.split()
    assert out == [os.path.join(REPO, ".jax_cache")] * 2


# -- the LM's attention auto rule -------------------------------------------


def test_auto_attention_rule_by_backend_and_shape():
    rule = gossip_lm.resolve_attention
    assert rule(None, 1024, 1, "cpu", LOG) == "full"
    assert rule(None, 1024, 1, "tpu", LOG) == "flash"
    assert rule(None, 1024, 2, "tpu", LOG) == "ring"
    # shape, and only shape, routes an auto-selected flash to blockwise
    assert rule(None, 200, 1, "tpu", LOG) == "blockwise"
    assert rule("blockwise", 1024, 1, "tpu", LOG) == "blockwise"
    with pytest.raises(SystemExit, match="--attn flash needs seq_len"):
        rule("flash", 200, 1, "tpu", LOG)


def test_no_exception_handler_around_the_flash_choice():
    """A kernel the chip's compiler rejects must fail the run, not be
    swapped for the blockwise reference behind a warning."""
    assert "except" not in inspect.getsource(gossip_lm.resolve_attention)
    source = inspect.getsource(gossip_lm)
    assert "_flash_compiles" not in source
    assert "except Exception" not in source
