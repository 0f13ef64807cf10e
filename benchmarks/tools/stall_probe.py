#!/usr/bin/env python3
"""Where does the host's stall come from?

    python3 benchmarks/tools/stall_probe.py <cell> <seed> <deadline_s> \
        <out.jsonl>

Drives the cell's program as ``run.py``'s window does in its second part
(single fenced steps) until ``deadline_s`` after the process's start,
set-up included, and round every step reads the clocks that tell a
stall's kinds apart:

- wall time of the dispatch and of the fence;
- the main thread's CPU time: a collector pass burns it, a wait does not;
- the thread's run-queue delay (``/proc/thread-self/schedstat``):
  contention for a core;
- the VM's steal and iowait (``/proc/stat``), CPU / memory / IO pressure
  (``/proc/pressure``), page faults;
- every collector pass with its generation and length (``gc.callbacks``);
- a ticker thread's lateness: late as well, and the interpreter lock was
  held or the whole process stood still; on time, and the main thread
  alone waited (its stack is kept).

One JSON line a step goes to ``out.jsonl``; the steps that took twice the
median (``SLOW``) and what the ticker saw (``LATE``) to stdout. Written
for the sparse cell's stalls (PERF.md section 6, PR 39: 4 in 19 windows,
0.7-2.8 s, the device idle under ``kfac.step.read_step``). Its one chip
run met none in 790 steps; a rehearsal cell runs it on the CPU.
"""

import gc
import json
import os
import sys
import threading
import time
import traceback

T0 = time.perf_counter()
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

STUCK_S = 0.45      # a plain step that long is a stall, not a step


def read(path):
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return ''


def _psi_total(kind):
    line = read('/proc/pressure/' + kind).split('\n')[0]
    return int(line.rsplit('total=', 1)[-1]) if 'total=' in line else -1


def clocks():
    """Every clock at once; all but ``wall`` are cumulative."""
    # cpu user nice system idle iowait irq softirq steal
    stat = read('/proc/stat').split('\n', 1)[0].split()
    # on-cpu ns, run-queue wait ns, slices
    sched = read('/proc/thread-self/schedstat').split()
    # after the name: minflt is field 7, majflt field 9
    pstat = read('/proc/self/stat').rsplit(')', 1)[-1].split()
    return {
        'wall': time.perf_counter() - T0,
        'thread_cpu': time.thread_time(),
        'proc_cpu': time.process_time(),
        'runq_wait_s': int(sched[1]) / 1e9 if len(sched) > 1 else -1,
        'steal': int(stat[8]) if len(stat) > 8 else -1,
        'iowait': int(stat[5]) if len(stat) > 5 else -1,
        'minflt': int(pstat[7]) if len(pstat) > 9 else -1,
        'majflt': int(pstat[9]) if len(pstat) > 9 else -1,
        'psi_cpu_us': _psi_total('cpu'),
        'psi_mem_us': _psi_total('memory'),
        'psi_io_us': _psi_total('io')}


def watch_collector():
    """-> the list every collector pass is appended to."""
    passes, started = [], {}

    def on_pass(phase, info):
        if phase == 'start':
            started['t'] = time.perf_counter()
        else:
            now = time.perf_counter()
            passes.append({'gen': info['generation'],
                           's': now - started['t'], 'at': now - T0,
                           'collected': info['collected']})
    gc.callbacks.append(on_pass)
    return passes


def ticker(beat, late, stop, main_id):
    """Wakes every 20 ms: records its own lateness, and the main thread's
    stack where a plain step has not come back for ``STUCK_S``."""
    last, told = time.perf_counter(), None
    while not stop.is_set():
        time.sleep(0.02)
        now = time.perf_counter()
        if now - last > 0.15:
            late.append({'ticker_late_s': now - last - 0.02,
                         'at': now - T0})
        if (now - beat['t'] > STUCK_S and told != beat['t']
                and beat['where'] != 'update'):
            told = beat['t']
            frame = sys._current_frames().get(main_id)
            late.append({
                'main_stuck_s': now - beat['t'], 'at': now - T0,
                'where': beat['where'],
                'stack': traceback.format_stack(frame)[-6:] if frame
                else None,
                'loadavg': read('/proc/loadavg').strip(),
                'clocks': clocks()})
        last = now


def gen2(passes):
    return [(round(p['s'], 3), round(p['at'], 1)) for p in passes
            if p['gen'] == 2]


def main():
    cell_name, seed = sys.argv[1], int(sys.argv[2])
    deadline, out_path = float(sys.argv[3]), sys.argv[4]
    passes = watch_collector()
    import numpy as np
    import run
    run.place_cache()
    from harness import files, program, window
    from kfac_pytorch_tpu.utils.profiling import host_fence

    cell, _ = files.resolve_workload(cell_name)
    cfg, _ = files.load_json('configs', cell['config'])
    traffic, _ = files.load_json('traffic', cell['traffic'])
    traffic = dict(traffic, chips=cell['chips'])
    builder = files.load_module('builders', cfg['builder'])
    plain = files.load_module('reference', cfg['plain'])
    prog = program.build(builder, plain, cfg, traffic, seed)
    stepper = window.Stepper(prog, host_fence)
    per = window.period(traffic)
    for _ in range(per):
        host_fence(stepper.step()['loss'])
    print(json.dumps({
        'setup_s': time.perf_counter() - T0,
        'tracked_objects': len(gc.get_objects()),
        'gen2_passes_in_setup': gen2(passes),
        'status': [line for line in read('/proc/self/status').split('\n')
                   if line.startswith(('VmRSS', 'VmHWM', 'Threads'))],
        'cgroup_mem_max': read('/sys/fs/cgroup/memory.max').strip(),
        'cgroup_mem_now': read('/sys/fs/cgroup/memory.current').strip(),
        'cgroup_cpu_max': read('/sys/fs/cgroup/cpu.max').strip(),
        'cgroup_cpu_stat': read('/sys/fs/cgroup/cpu.stat').split('\n')[:6],
    }), flush=True)

    late, stop = [], threading.Event()
    beat = {'t': time.perf_counter(), 'where': 'start'}
    threading.Thread(target=ticker, daemon=True, args=(
        beat, late, stop, threading.get_ident())).start()
    rows, seen = [], len(passes)
    with open(out_path, 'w') as out:
        while time.perf_counter() - T0 < deadline:
            for _ in range(per):
                update = stepper.count % per == 0
                before = clocks()
                beat.update(t=time.perf_counter(),
                            where='update' if update else 'dispatch')
                mets = stepper.step()
                dispatched = clocks()
                beat['where'] = 'update' if update else 'fence'
                host_fence(mets['loss'])
                after = clocks()
                beat.update(t=time.perf_counter(), where='between')
                row = {'step': stepper.count, 'update': update,
                       'dispatch_s': dispatched['wall'] - before['wall'],
                       'fence_s': after['wall'] - dispatched['wall'],
                       'passes': passes[seen:]}
                row.update({'d_' + k: after[k] - before[k]
                            for k in before if k != 'wall'})
                seen = len(passes)
                rows.append(row)
                out.write(json.dumps(row) + '\n')
            out.flush()
        stop.set()
        out.write(json.dumps({'late': late}) + '\n')

    took = {u: np.array([r['dispatch_s'] + r['fence_s'] for r in rows
                         if r['update'] is u]) for u in (False, True)}
    slow = [r for r in rows if r['dispatch_s'] + r['fence_s']
            > 2 * np.median(took[r['update']])]
    print(json.dumps({
        'steps': len(rows), 'wall': time.perf_counter() - T0,
        'plain_median_s': float(np.median(took[False])),
        'plain_max_s': float(took[False].max()),
        'update_median_s': float(np.median(took[True])),
        'update_max_s': float(took[True].max()),
        'slow_steps': len(slow), 'gen2_passes': gen2(passes),
        'cgroup_cpu_stat': read('/sys/fs/cgroup/cpu.stat').split('\n')[:6],
        'cgroup_mem_now': read('/sys/fs/cgroup/memory.current').strip()}))
    for row in slow[:12]:
        print('SLOW', json.dumps(row)[:900])
    for row in late[:20]:
        print('LATE', json.dumps(row)[:1500])


if __name__ == '__main__':
    main()
