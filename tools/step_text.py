#!/usr/bin/env python3
"""Whether two compiled step programs are the same program but for where
their instructions came from.

    python tools/step_text.py <a.txt> <b.txt>

Each file is a ``compile().as_text()`` (of the fused training step: what
the benchmark's runners hand their readers as ``hlo_text``). Taken out of
both before they are compared: every ``metadata={...}``, the tables of
files, functions, locations and stack frames at the head, and the debug
locations inside the Pallas kernels' serialized bodies (MLIR bytecode,
compared as assembly without them). A ``jax.named_scope`` may change
nothing else: instructions, fusions and schedule are then the same. Exit
code 1 where they are not.
"""
import base64
import hashlib
import re
import sys

METADATA = re.compile(r', metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


def stripped(path):
    lines, skip = [], False
    with open(path) as f:
        for line in f:
            if line.strip() in TABLES:
                skip = True
            elif skip and not line.strip():
                skip = False
            elif not skip:
                lines.append(line)
    text, n = METADATA.subn("", "".join(lines))
    print("%s: %d lines, metadata taken from %d instructions, sha256 %s" % (
        path, len(lines), n, hashlib.sha256(text.encode()).hexdigest()[:16]))
    return text.splitlines()


def kernel(body):
    """A Mosaic kernel's body as assembly without its debug locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        return ir.Module.parse(base64.b64decode(body)).operation.get_asm(
            enable_debug_info=False)


def same(a, b):
    one, other = stripped(a), stripped(b)
    differ = abs(len(one) - len(other))
    kernels = 0
    for n, (x, y) in enumerate(zip(one, other), 1):
        if x == y:
            continue
        bx, by = BODY.search(x), BODY.search(y)
        if bx and by and BODY.sub("", x) == BODY.sub("", y) \
                and kernel(bx.group(1)) == kernel(by.group(1)):
            kernels += 1
            continue
        differ += 1
        print("line %d differs: %s" % (n, x[:120].strip()))
    print("DIFFERENT in %d lines" % differ if differ else
          "IDENTICAL (%d kernel bodies differ in their debug locations "
          "alone)" % kernels)
    return differ == 0


if __name__ == "__main__":
    if len(sys.argv) != 3:
        sys.exit(__doc__)
    sys.exit(0 if same(*sys.argv[1:]) else 1)
