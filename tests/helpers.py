"""Shared test helpers."""

import socket
import subprocess

from kfac_pytorch_tpu.models.tiny import TinyCNN  # noqa: F401 (re-export)


def free_port():
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


def run_two_process(argv_fn, env, tag):
    """Spawn two coordinated jax.distributed workers, collect both
    outputs, assert both exited 0 and printed an identical ``tag`` line
    (the cross-process agreement check every multihost drill ends with).
    ``argv_fn(pid) -> argv list``; ``env`` gets JAX_PROCESS_ID added per
    worker. Returns the two full outputs."""
    procs = []
    try:
        for pid in range(2):
            procs.append(subprocess.Popen(
                argv_fn(pid), env=dict(env, JAX_PROCESS_ID=str(pid)),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        outs = communicate_all(procs)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out[-2000:]
    lines = []
    for i, o in enumerate(outs):
        tagged = [l for l in o.splitlines() if l.startswith(tag)]
        # a worker can exit 0 without ever reaching the tag print (e.g. a
        # skipped drill body); indexing [-1] directly would surface that
        # as an opaque IndexError with no worker output (ADVICE r4)
        assert tagged, (f'worker {i} exited 0 but never printed a '
                        f'{tag!r} line; output tail: {o[-2000:]}')
        lines.append(tagged[-1])
    assert lines[0] == lines[1], lines
    return outs


def communicate_all(procs, timeout=450):
    """communicate() with every process of a multi-process drill; on any
    timeout, kill them all and surface EVERY worker's output — the stuck
    worker is usually blocked on a failed peer's init barrier, so the
    root cause lives in the peer's stdout."""
    outs = []
    for p in procs:
        try:
            outs.append(p.communicate(timeout=timeout)[0])
        except subprocess.TimeoutExpired:
            for q in procs:
                if q.poll() is None:
                    q.kill()
            everything = list(outs)
            for q in procs[len(outs):]:
                everything.append(q.communicate()[0])
            raise AssertionError(
                f'worker timed out; all outputs: {everything}')
    return outs
