"""CPU rehearsal of chip_smoke.py: its phase functions at a tiny size on the
virtual CPU devices, so the script cannot rot between chip runs.

The script itself has no CPU mode; the TPU requirement is steered HERE.
Each ``check_*`` is expected to pass everything a CPU run can show (finite
falling loss, greedy tokens that agree with the reference, no compile after
``warmup()``, a prefix hit, chunked prefill, four devices holding shards)
and to FAIL exactly the chip-only checks (compiled kernel in the program
text, arrays on a TPU, donation, device memory stats) — which also shows
that those checks bite.  "Equal to ``net.generate``" means, in bf16, every
served token within a stated tolerance of a float32 reference's argmax.
"""
import os
import subprocess
import sys

import jax
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke as cs  # noqa: E402

# GPT-2's shape in miniature: odd vocabulary, heads that tp=4 divides
TINY = cs.Size(model=dict(vocab_size=257, units=64, num_layers=1,
                          num_heads=4, max_length=64),
               batch=8, seq=32, seq_bucket=16, page_size=8, prefix_len=10,
               tail_len=4, long_len=40, short_len=5, short_new=12, new=4)


# what a CPU run cannot satisfy, by the words of the check that reports it
_CHIP_ONLY = {
    "train": ("no flash kernel", "score tensors", "live arrays",
              "donation off"),
    "serve": ("no paged kernel",),
    "mesh": ("memory_stats()", "no flash kernel"),
}


@pytest.fixture(scope="module")
def facts(mesh_devices_module):
    """Each phase run once at the tiny size, with its check's verdict."""
    out = {"train": cs.run_train(TINY, seed=0),
           "serve": cs.run_serve(TINY, seed=0),
           "mesh": cs.run_mesh(TINY, seed=0, devices=mesh_devices_module)}
    checks = {"train": cs.check_train, "serve": cs.check_serve,
              "mesh": cs.check_mesh}
    return {k: (v, checks[k](v)) for k, v in out.items()}


@pytest.fixture(scope="module")
def mesh_devices_module():
    """conftest's ``mesh_devices(4)``, once for the module."""
    from mxnet_tpu.test_utils import mesh_devices
    devs = mesh_devices(4)
    if devs is None:
        pytest.skip("needs 4 XLA devices (tests/conftest.py gives 8)")
    return devs


@pytest.mark.parametrize("phase", ["train", "serve", "mesh"])
def test_phase_passes_what_a_cpu_run_can_show(facts, phase):
    _, failures = facts[phase]
    assert [f for f in failures
            if not any(c in f for c in _CHIP_ONLY[phase])] == []


@pytest.mark.parametrize("phase", ["train", "serve", "mesh"])
def test_chip_only_checks_bite_on_the_cpu(facts, phase):
    _, failures = facts[phase]
    for words in _CHIP_ONLY[phase]:
        assert any(words in f for f in failures), (words, failures)


def test_bf16_tolerance_rejects_a_wrong_token(facts):
    """The tolerance that stands in for token equality in bf16 must not
    pass a wrong token: swap one served token for another id."""
    seen, _ = facts["serve"]
    assert seen["xla_compiles_on_traffic"] == 0
    prompts = cs._prompts(TINY, 257, 0)
    served = [p.tolist() + new
              for (p, _), new in zip(prompts, seen["new_tokens"])]
    served[3][-2] = (served[3][-2] + 101) % 257
    margins = cs._greedy_margins(TINY, 0, served, prompts)
    assert margins[3] > cs._BF16_LOGIT_TOL >= max(margins[:3])


def test_mesh_phase_spreads_over_four_devices(facts):
    seen, _ = facts["mesh"]
    assert seen["serve_mesh4"]["paged_attention"] == "gather"
    assert min(seen["shard_bytes_per_device"]) > 0
    # 257 rows do not divide over tp=2: the embedding replicates
    assert seen["embedding_layout"]["shard_shapes"] == [(257, 64)]


def test_main_refuses_a_process_without_a_tpu(capsys):
    with pytest.raises(Exception, match="no TPU"):
        cs.main([])
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("script", ["chip_smoke.py"])
def test_entry_points_fail_without_a_tpu(script):
    """As the driver runs them in a sandbox: non-zero, the message names
    the missing TPU, and nothing that could be read as a result.  The
    other chip entry point, chipbench/run.py, is held to the same by
    tests/chipbench/test_chipbench_cells.py."""
    r = subprocess.run([sys.executable, os.path.join(_REPO, script)],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       cwd=_REPO, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout == ""


def test_tpu_context_is_an_error_without_an_accelerator():
    import mxnet_tpu as mx
    assert jax.default_backend() == "cpu"
    with pytest.raises(mx.MXNetError, match="no accelerator"):
        mx.tpu(0).jax_device
