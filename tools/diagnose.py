#!/usr/bin/env python
"""Diagnose script: OS / hardware / python / pip / mxnet_tpu / device checks.

Parity: /root/reference/tools/diagnose.py (its output is "a very good hint
to issue/problem"). TPU-native differences: the device section probes the
PJRT backend in a child process with a timeout (a chip held by another
process can block instead of failing), the mxnet section reports the typed flag registry instead of
env-var sprawl, and network checks default OFF (TPU pods are commonly
egress-less; the reference pinged mxnet.io et al. by default).

Usage: python tools/diagnose.py [--python 1] [--pip 1] [--mxnet 1]
       [--os 1] [--hardware 1] [--device 1] [--network 0]
       [--timeout 20] [--region us]
"""
import argparse
import os
import platform
import socket
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REGION_URLS = {
    "us": ["https://pypi.org", "https://github.com"],
    "cn": ["https://pypi.tuna.tsinghua.edu.cn", "https://gitee.com"],
}


def parse_args():
    p = argparse.ArgumentParser(
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
        description="Diagnose the current system for bug reports.")
    for choice in ("python", "pip", "mxnet", "os", "hardware", "device"):
        p.add_argument("--" + choice, default=1, type=int,
                       help="Diagnose %s." % choice)
    p.add_argument("--network", default=0, type=int,
                   help="Diagnose network (off by default: TPU hosts are "
                        "often egress-less).")
    p.add_argument("--region", default="us", choices=list(REGION_URLS),
                   help="Url region for the network test.")
    p.add_argument("--timeout", default=20, type=int,
                   help="Seconds before a probe (device init, url) is "
                        "declared hung.")
    return p.parse_args()


def check_python():
    print("----------Python Info----------")
    print("Version      :", platform.python_version())
    print("Compiler     :", platform.python_compiler())
    print("Build        :", platform.python_build())
    print("Arch         :", platform.architecture())


def check_pip():
    print("------------Pip Info-----------")
    try:
        import pip
        print("Version      :", pip.__version__)
        print("Directory    :", os.path.dirname(pip.__file__))
    except ImportError:
        print("No corresponding pip install for current python.")


def check_mxnet():
    print("----------mxnet_tpu Info-----------")
    try:
        t0 = time.time()
        import mxnet_tpu as mx
        print("Version      :", mx.__version__)
        print("Directory    :", os.path.dirname(mx.__file__))
        print("Import time  : %.3f s" % (time.time() - t0))
        for name in ("jax", "jaxlib", "flax", "optax", "numpy"):
            try:
                m = __import__(name)
                print("%-13s: %s" % (name, getattr(m, "__version__", "?")))
            except ImportError:
                print("%-13s: not installed" % name)
        from mxnet_tpu.config import flags, describe
        non_default = {d["name"]: getattr(flags, d["name"])
                       for d in describe()
                       if getattr(flags, d["name"]) != d["default"]}
        print("Flags (non-default):", non_default or "none")
    except ImportError as e:
        print("No mxnet_tpu installed:", e)
    except Exception as e:  # pragma: no cover - env-specific
        print("An error occurred trying to import mxnet_tpu.")
        print(e)


def check_os():
    print("----------System Info----------")
    print("Platform     :", platform.platform())
    print("system       :", platform.system())
    print("node         :", platform.node())
    print("release      :", platform.release())
    print("version      :", platform.version())


def check_hardware():
    print("----------Hardware Info----------")
    print("machine      :", platform.machine())
    print("processor    :", platform.processor())
    if sys.platform.startswith("linux"):
        try:
            out = subprocess.check_output(["lscpu"], text=True)
            for line in out.splitlines():
                if line and not line.startswith("Flags"):
                    print(line)
        except Exception:
            pass


def check_device(timeout):
    """Probe the PJRT backend in a subprocess so a backend that blocks
    (a chip held by another process) cannot hang the diagnosis itself.
    This process never touches JAX, so the child may take the chip."""
    print("----------Device Info----------")
    code = ("import jax, json; d = jax.devices(); "
            "print(json.dumps([{'kind': x.device_kind, "
            "'platform': x.platform, 'id': x.id} for x in d]))")
    t0 = time.time()
    try:
        out = subprocess.run([sys.executable, "-c", code], timeout=timeout,
                             capture_output=True, text=True)
        dt = time.time() - t0
        tail = [ln for ln in out.stdout.strip().splitlines() if ln]
        if out.returncode == 0 and tail:
            print("Devices      :", tail[-1])
            print("Init time    : %.1f s" % dt)
        else:
            print("Device init FAILED (rc=%d) after %.1f s" % (
                out.returncode, dt))
            if out.stderr:
                print(out.stderr.strip().splitlines()[-1])
    except subprocess.TimeoutExpired:
        print("Device init HUNG (> %d s) — backend unreachable or held"
              % timeout)
    print("JAX_PLATFORMS:", os.environ.get("JAX_PLATFORMS", "<unset>"))


def test_connection(name, url, timeout):
    from urllib.request import urlopen
    from urllib.parse import urlparse
    try:
        ip = socket.gethostbyname(urlparse(url).netloc)
        t0 = time.time()
        urlopen(url, timeout=timeout)
        print("Timing for %s: %s, DNS: %s, LOAD: %.4f sec."
              % (name, url, ip, time.time() - t0))
    except Exception as e:
        print("Error open %s: %s %s, DNS finished in %s sec."
              % (name, url, e, time.time() - t0 if "t0" in dir() else "?"))


def check_network(args):
    print("----------Network Test----------")
    socket.setdefaulttimeout(10)
    for url in REGION_URLS[args.region]:
        test_connection(url, url, args.timeout)


if __name__ == "__main__":
    args = parse_args()
    if args.python:
        check_python()
    if args.pip:
        check_pip()
    if args.mxnet:
        check_mxnet()
    if args.os:
        check_os()
    if args.hardware:
        check_hardware()
    if args.device:
        check_device(args.timeout)
    if args.network:
        check_network(args)
