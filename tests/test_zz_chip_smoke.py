"""chip_smoke.py's phase functions, rehearsed at tiny widths on the CPU
mesh, and the one-process-per-chip rules they stand on.

The script has no CPU mode and no option that gives it one: the tests
steer from outside. TPU_ACCELERATOR_TYPE makes the node daemon advertise
chips this host does not have, XLA_FLAGS gives each leased worker as many
virtual CPU devices as it was leased chips — and the phase CHECKS are left
as they are, so every rehearsal must end in exactly the failures that say
"this was not a TPU" and in no other.

(The file is named to run last: the two rehearsals boot seven clusters and
are the most expensive tests in the suite — a run that hits its time limit
should lose these before anything else. The `--chips 4` rehearsal stands in
tests/test_zz_chip_smoke_4chips.py: a file is what a worker of the suite is
handed, and two workers share the two rehearsals.)
"""

import os
import subprocess
import sys

import pytest

TINY = dict(vocab_size=256, dim=64, n_heads=8, n_kv_heads=4, ffn_dim=128,
            rope_theta=10000.0)
SERVE = {
    "model": {**TINY, "n_layers": 2, "param_dtype": "bfloat16"},
    "engine": {"page_size": 8, "total_pages": 64, "max_batch": 4,
               "max_seq_len": 128, "prefill_chunk": 16},
    "prompt_lens": [12, 20, 9, 33], "max_tokens": 6, "because": "test",
}
TRAIN = {
    "model": {**TINY, "n_layers": 2, "attention": "flash"},
    "batch": 2, "seq_len": 64, "steps": 3, "lr": 1e-2, "because": "test",
}

NOT_A_TPU = {
    "serve": ["serve: replica ran on platform 'cpu'",
              "serve: paged attention impl 'reference'"],
    "train": ["train: worker ran on platform 'cpu'",
              "train: attention='flash' resolved to 'blockwise'",
              "train: the lowered step holds 0 tpu_custom_calls"],
}


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env():
    """os.environ, spelled out: a child given no env= inherits the C-level
    environment instead, and libtpu setenv()s TPU_* placeholders there
    once anything (tests/_tpu_compile.py) has described a topology —
    "WARNING: could not determine ..." strings no daemon can parse."""
    return dict(os.environ)


def _only_not_a_tpu(failures, expected, also=()):
    """Each expected not-a-TPU failure is there, and nothing else is
    (but for `also`: prefixes that may repeat, e.g. one per device)."""
    rest = list(failures)
    for prefix in expected:
        hit = [f for f in rest if f.startswith(prefix)]
        assert len(hit) == 1, (prefix, failures)
        rest.remove(hit[0])
    rest = [f for f in rest if not f.startswith(tuple(also))]
    assert not rest, rest


@pytest.fixture
def fake_chips(monkeypatch):
    def arm(n_devices):
        monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
        monkeypatch.setenv(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={n_devices}")
    return arm


def _driver_backend_untouched():
    from jax._src import xla_bridge
    return not xla_bridge.backends_are_initialized()


def test_one_chip_phases_rehearsed_on_cpu(fake_chips):
    """device -> serve -> train, one cluster session each, through the
    script's own phase functions. Run in a fresh interpreter: the claim
    "the parent never initialises a jax backend" is about a process that
    did nothing else (this pytest process has used jax long ago)."""
    fake_chips(1)
    code = (
        "import json, sys\n"
        "import chip_smoke as cs, tests.test_zz_chip_smoke as t\n"
        "dev = cs.run_device(1)\n"
        "srv = cs.run_serve(t.SERVE, seed=3)\n"
        "trn = cs.run_train(t.TRAIN, seed=3)\n"
        "print('RESULT', json.dumps({\n"
        "  'device': cs.check_device(dev, 1), 'dev': dev['device'],\n"
        "  'serve': cs.check_serve(srv, t.SERVE), 'srv': srv,\n"
        "  'train': cs.check_train(trn, t.TRAIN), 'trn': trn,\n"
        "  'untouched': t._driver_backend_untouched()}, default=str))\n")
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=300, env=_env(), cwd=_REPO)
    except subprocess.TimeoutExpired as e:
        pytest.fail(f"phases still running after 300 s:\n"
                    f"{(e.stderr or b'')[-4000:]!r}")
    assert proc.returncode == 0, proc.stderr[-4000:]
    import json
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    res = json.loads(line[len("RESULT "):])

    assert res["untouched"], "the driver initialised a jax backend"
    assert res["device"] == ["device: leased worker sees platform 'cpu', "
                             "not 'tpu'"]
    assert res["dev"]["count"] == 1
    _only_not_a_tpu(res["serve"], NOT_A_TPU["serve"])
    _only_not_a_tpu(res["train"], NOT_A_TPU["train"])

    srv, trn = res["srv"], res["trn"]
    assert srv["tokens_out"] == 6 * 4
    # the replica compiled every program before its first request
    assert srv["compiled_step_programs"] == srv["step_program_budget"] == 4
    assert all(c["equal"] and c["max_gap"] == 0.0
               for c in srv["plain_check"])
    assert srv["worker_pid"] != srv["driver_pid"]
    losses = trn["runs"]["mesh"]["losses"]
    assert len(losses) == 3 and losses[-1] < losses[0]
    assert trn["checkpoint_bytes"] >= trn["runs"]["mesh"]["param_bytes"]
    assert trn["worker_pid"] != trn["driver_pid"]


def test_no_chip_means_no_result():
    """Where the cluster finds no chip the script exits non-zero and its
    stdout holds nothing: no result line, no daemon chatter."""
    env = _env()
    env.pop("TPU_ACCELERATOR_TYPE", None)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], capture_output=True, text=True,
        timeout=300, env=env, cwd=_REPO)
    assert proc.returncode == 1
    assert proc.stdout == ""
    assert "no accelerator here" in proc.stderr


def test_driver_that_imported_train_never_claims_the_chip():
    """Regression: the telemetry flush asked jax.local_devices() whenever
    jax was merely imported — so a driver that did `from ray_tpu import
    train` (the README quick start) initialised the backend, i.e. took the
    chip, on its first flush, and the train worker could not."""
    code = (
        "import sys\n"
        "import ray_tpu as rt\n"
        "from ray_tpu import train  # imports jax\n"
        "assert 'jax' in sys.modules\n"
        "rt.init(num_cpus=1)\n"
        "from ray_tpu.core.worker import global_worker\n"
        "from ray_tpu.runtime.hw_sampler import tpu_memory_samples\n"
        "global_worker.backend._flush_telemetry()\n"
        "assert tpu_memory_samples() == []\n"
        "rt.shutdown()\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized(), 'claimed'\n"
        "import jax; jax.devices()\n"
        "assert xla_bridge.backends_are_initialized()\n"
        "print('UNCLAIMED')\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, env=_env(), cwd=_REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "UNCLAIMED" in proc.stdout


def test_generic_workers_are_held_to_the_cpu_on_a_tpu_host(
        fake_chips, monkeypatch):
    """On a host with chips only a worker leased TPU resources keeps the
    ambient jax platform; every other worker gets JAX_PLATFORMS=cpu."""
    import ray_tpu as rt
    fake_chips(1)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")  # a chip machine's
    try:
        rt.init(num_cpus=2)

        def platforms():
            return os.environ.get("JAX_PLATFORMS"), \
                os.environ.get("TPU_VISIBLE_CHIPS")

        generic = rt.get(rt.remote(num_cpus=1)(platforms).remote(),
                         timeout=120)
        leased = rt.get(rt.remote(num_cpus=1, resources={"TPU": 1.0})(
            platforms).remote(), timeout=120)
    finally:
        rt.shutdown()
    assert generic == ("cpu", None)
    assert leased == ("tpu,cpu", "0")


@pytest.mark.parametrize("placed", ["/somewhere/else", None])
def test_compile_cache_is_placed_from_outside(monkeypatch, placed):
    """JAX_COMPILATION_CACHE_DIR set: that directory, and no path set in
    code. Unset: ONE fixed directory inside the checkout, exported so that
    child processes compile into the same cache."""
    import jax

    from ray_tpu.util import compile_cache
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.append((k, v)))
    if placed:
        monkeypatch.setenv(compile_cache.ENV_VAR, placed)
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    got = compile_cache.configure()
    assert os.environ[compile_cache.ENV_VAR] == got
    if placed:
        assert got == placed and seen == []
    else:
        assert got == os.path.join(_REPO, ".jax_cache")
        assert seen == [("jax_compilation_cache_dir", got)]
        assert compile_cache.configure() == got      # and it stays put
