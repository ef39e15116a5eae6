"""``chip_smoke.py`` at CPU size: the same phase functions the chip run
drives at full width, on tiny models — wrong paths, arguments and
control flow show here, at no chip time. (What only the chip can show —
kernels that compile, memory that fits — is tests/test_tpu_compile.py's
and the chip run's business: ``expect_kernels`` is off.)"""

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from apex_tpu import _backend
from apex_tpu.models.gpt import GPTConfig


@pytest.fixture(scope="module")
def watch():
    return chip_smoke.CompileWatch()


def tiny(**kw):
    base = dict(vocab_size=128, max_seq_len=16, hidden_size=32,
                num_layers=1, num_heads=4, num_kv_heads=2,
                attention_backend="flash", dtype=jnp.float32)
    base.update(kw)
    return GPTConfig(**base)


def test_refuses_to_run_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert "no TPU" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_trainer_and_optimizer_phases(watch, capsys):
    out = chip_smoke.train_phase(tiny(), batches=(4, 2), steps=3,
                                 watch=watch, expect_kernels=False,
                                 lr=1e-3)
    # the CPU reports no memory limit: the first candidate is taken
    assert out["batch"] == 4 and out["losses"][-1] < out["losses"][0]
    chip_smoke.lamb_phase(tiny(), steps=2, watch=watch,
                          expect_kernels=False)
    said = capsys.readouterr().out
    assert "batch chosen: 4" in said and "FusedLAMB took 2 steps" in said


def test_server_phase(watch, capsys):
    out = chip_smoke.serve_phase(
        tiny(max_seq_len=128), num_blocks=64, max_batch=4,
        prefill_chunk=16, past=64, watch=watch, expect_kernels=False,
        block_size=8, min_width_bucket=2, min_seq_bucket=8,
        requests=[("short", 6, 5), ("chunked", 40, 3), ("long", 60, 10)])
    assert out["tokens"] == {"short": 5, "chunked": 3, "long": 10}
    assert out["programs"]["prefill_chunk"] >= 1
    said = capsys.readouterr().out
    # every request is held to model.apply, the chunked and the long too
    for exact in ("5 of 5", "3 of 3", "10 of 10"):
        assert f"{exact} served tokens" in said


def test_greedy_reference_refuses_a_wrong_token():
    import numpy as np

    from apex_tpu.models.gpt import GPTModel

    cfg = tiny(max_seq_len=128)
    model = GPTModel(cfg)
    prompt = np.arange(1, 7)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.asarray(prompt[None], jnp.int32))
    served = []
    for _ in range(2):      # the reference's own greedy continuation
        toks = np.concatenate([prompt, served]).astype(np.int32)[None]
        served.append(int(jnp.argmax(model.apply(params, toks)[-1, 0])))
    served = np.asarray(served)
    assert chip_smoke.greedy_reference(
        model, params, prompt, served, cfg.dtype)[0] == 2
    served[1] = (served[1] + 1) % cfg.vocab_size
    with pytest.raises(AssertionError, match="token 1 trails"):
        chip_smoke.greedy_reference(model, params, prompt, served,
                                    cfg.dtype)


def test_server_phase_fails_on_an_unanswered_request(watch):
    # a request that can never fit the pool is rejected by the engine:
    # the phase must fail, not report it and carry on
    with pytest.raises(AssertionError, match="did not finish"):
        chip_smoke.serve_phase(
            tiny(max_seq_len=128), num_blocks=4, max_batch=4,
            prefill_chunk=16, past=64, watch=watch, expect_kernels=False,
            block_size=8, min_width_bucket=2, min_seq_bucket=8,
            requests=[("short", 6, 5), ("long", 60, 10)])


def test_mesh_phase_with_kernel_islands(watch, monkeypatch, capsys):
    # interpret mode puts the Pallas kernels in the program, so the
    # 2x2 mesh step runs them as shard_map islands (annotate.on_shards)
    # and must still agree with the one-device run
    monkeypatch.setenv("APEX_TPU_IMPL", "interpret")
    _backend.default_impl.cache_clear()
    try:
        assert len(jax.devices()) >= 4
        out = chip_smoke.mesh_phase(
            tiny(), batch_axis=2, model_axis=2,
            batches=(4,), steps=3, watch=watch, expect_kernels=False,
            lr=1e-3)
    finally:
        _backend.default_impl.cache_clear()
    assert max(abs(a - b) for a, b in
               zip(out["losses"], out["reference"])) < 1e-4
    said = capsys.readouterr().out
    assert "device ids in mesh order: [0, 1, 2, 3]" in said
    assert "all-reduce" in said
