"""Typed config/flag registry (SURVEY §5: unify env_var.md sprawl +
DMLC_DECLARE_PARAMETER into one introspectable registry)."""
import os
import subprocess
import sys

import pytest

from mxnet_tpu import config


def test_defaults_and_describe():
    rows = {r["name"]: r for r in config.describe()}
    assert rows["enable_x64"]["env"] == "MXNET_ENABLE_X64"
    assert rows["engine_type"]["value"] in ("ThreadedEngine", "NaiveEngine")
    for r in rows.values():
        assert r["doc"]  # every flag is documented


def test_env_parsing_and_reload():
    os.environ["MXNET_CPU_WORKER_NTHREADS"] = "7"
    try:
        config.flags.reload("cpu_worker_nthreads")
        assert config.flags.cpu_worker_nthreads == 7
    finally:
        del os.environ["MXNET_CPU_WORKER_NTHREADS"]
        config.flags.reload("cpu_worker_nthreads")
    assert config.flags.cpu_worker_nthreads == 4


def test_override_context():
    assert config.flags.enforce_determinism is False
    with config.override(enforce_determinism=True):
        assert config.flags.enforce_determinism is True
    assert config.flags.enforce_determinism is False
    with pytest.raises(KeyError):
        with config.override(not_a_flag=1):
            pass


def test_unknown_flag_raises():
    with pytest.raises(AttributeError):
        config.flags.nope


def test_the_scans_three_flags_are_gone_from_the_code_and_the_docs():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    docs = "".join(open(os.path.join(root, "docs", f)).read()
                   for f in ("data.md", "perf.md"))
    for name, env in (("steps_per_dispatch", "MXNET_STEPS_PER_DISPATCH"),
                      ("data_staged_feed", "MXNET_DATA_STAGED_FEED"),
                      ("data_feed_depth", "MXNET_DATA_FEED_DEPTH")):
        with pytest.raises(AttributeError):
            getattr(config.flags, name)
        assert env not in docs, env


def test_enforce_determinism_blocks_autoseed():
    code = (
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import mxnet_tpu as mx\n"
        "try:\n"
        "    mx.random.next_key()\n"
        "except RuntimeError as e:\n"
        "    assert 'MXNET_ENFORCE_DETERMINISM' in str(e)\n"
        "    mx.random.seed(7)\n"
        "    mx.random.next_key()\n"  # seeded: fine
        "    print('BLOCKED_THEN_OK')\n")
    env = dict(os.environ, MXNET_ENFORCE_DETERMINISM="1")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "BLOCKED_THEN_OK" in r.stdout


def test_compile_cache_persists_programs(tmp_path):
    """JAX_COMPILATION_CACHE_DIR places the cache from outside: the
    package sets no directory of its own, compiled programs persist
    there, and a later process reuses them."""
    cache = str(tmp_path / "xla_cache")
    code = (
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import mxnet_tpu as mx\n"
        "print('CACHE_DIR', jax.config.jax_compilation_cache_dir)\n"
        "net = mx.gluon.nn.Dense(8)\n"
        "net.initialize()\n"
        "net.hybridize()\n"
        "y = net(mx.nd.ones((4, 16)))\n"
        "y.asnumpy()\n"
        "print('RAN_OK')\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=180)
    assert r.returncode == 0, r.stderr
    assert "RAN_OK" in r.stdout
    assert "CACHE_DIR %s\n" % cache in r.stdout
    entries = os.listdir(cache)
    assert entries, "no programs persisted to the compilation cache"
    # a second process must HIT the cache (jax logs a cache read at debug;
    # cheaper check: the entry set does not grow for the same program)
    r2 = subprocess.run([sys.executable, "-c", code], capture_output=True,
                        text=True, env=env, timeout=180)
    assert r2.returncode == 0, r2.stderr
    assert set(os.listdir(cache)) == set(entries)


@pytest.mark.parametrize("platforms,expected", [
    ("cpu", None), ("cpu,tpu", None), ("tpu,cpu", ".jax_cache"),
    (None, ".jax_cache")])
def test_compile_cache_default_dir(platforms, expected):
    """With no directory given from outside, the cache is
    <checkout>/.jax_cache (from the package's own path: never ~, a temp
    name, a pid or a time), and off for a CPU-pinned process. Import
    only: no backend is touched, so this runs without a chip."""
    code = ("import jax, mxnet_tpu\n"
            "print('CACHE_DIR', jax.config.jax_compilation_cache_dir)\n")
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS")}
    if platforms is not None:
        env["JAX_PLATFORMS"] = platforms
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = None if expected is None else os.path.join(root, expected)
    assert "CACHE_DIR %s\n" % want in r.stdout


def test_misc_parity_modules():
    """util/log/libinfo/rtc parity slots (reference python/mxnet/)."""
    import mxnet_tpu as mx
    import tempfile, os
    d = os.path.join(tempfile.mkdtemp(), "a", "b")
    mx.util.makedirs(d)
    assert os.path.isdir(d)
    lg = mx.log.get_logger("parity_test", level=mx.log.INFO)
    assert lg.level == mx.log.INFO
    assert mx.libinfo.find_lib_path()[0].endswith("libmxtpu.so")
    assert mx.libinfo.find_include_path().endswith("src")
    import pytest as _pytest
    with _pytest.raises(mx.MXNetError, match="pallas"):
        mx.rtc.CudaModule("foo")


def test_generic_registry():
    """mx.registry factory trio (reference registry.py:49-175)."""
    import mxnet_tpu as mx

    class Base:
        def __init__(self, x=1):
            self.x = x

    register = mx.registry.get_register_func(Base, "thing")
    alias = mx.registry.get_alias_func(Base, "thing")
    create = mx.registry.get_create_func(Base, "thing")

    @alias("alt")
    @register
    class MyThing(Base):
        pass

    assert isinstance(create("mything"), MyThing)
    assert isinstance(create("alt", 5), MyThing)
    inst = MyThing(2)
    assert create(inst) is inst
    made = create('["mything", {"x": 7}]')  # JSON form
    assert made.x == 7
    made2 = create({"thing": "mything", "x": 3})
    assert made2.x == 3
    import pytest as _pytest
    with _pytest.raises(AssertionError, match="not registered"):
        create("nope")


def test_log_file_handler_has_no_ansi(tmp_path):
    import mxnet_tpu as mx
    path = str(tmp_path / "run.log")
    lg = mx.log.get_logger("ansi_test", filename=path, level=mx.log.INFO)
    lg.warning("hello")
    for h in lg.handlers:
        h.flush()
    content = open(path).read()
    assert "hello" in content and "\x1b[" not in content


def test_server_role_shims():
    """A server/scheduler-role process exits 0 AT IMPORT (reference
    kvstore_server.py:85 contract) instead of running the training
    script; legacy executor-manager imports point at the SPMD
    replacement."""
    import subprocess, sys
    code = ("import jax; jax.config.update('jax_platforms','cpu');"
            "import mxnet_tpu;"
            "print('MUST NOT REACH: training script ran on a server')")
    env = dict(os.environ, DMLC_ROLE="server")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0
    assert "MUST NOT REACH" not in r.stdout
    assert "no parameter servers" in r.stderr

    import mxnet_tpu as mx
    import pytest as _pytest
    with _pytest.raises(mx.MXNetError, match="SPMD"):
        mx.executor_manager.DataParallelExecutorManager()
