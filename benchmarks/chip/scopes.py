"""Per-layer device time from the program's own scopes and host spans.

The program names its compute layers with ``jax.named_scope('qmc.<layer>')``
(``repro.core``) and what its host does with ``qmc.`` profiler spans
(``repro.tracing``).  ``load`` reads a traced ``.xplane.pb``:

* each device op with its HLO name and its ``tf_op`` path (the op's scope
  path, e.g. ``jit(<lambda>)/while/body/vmap(qmc.slater)/jit(inv)/lu:``),
  a stat of the op's event metadata that ``jax.profiler.ProfileData`` does
  not expose, so the XSpace proto is parsed here with ``google.protobuf``
  and a minimal descriptor of the fields it needs (no TensorFlow);
* the host's ``qmc.`` spans and the benchmark's ``bench.window``.

``reduce`` then works on plain tuples in nanoseconds:

* an op's layer is the innermost ``qmc.<layer>`` on its path;
  ``qmc.block_stats`` is a phase, not a layer, and is counted inclusively;
  loops and calls (``trace.CONTAINERS``) hold other ops and are left out;
* an op with no path at all was made by a compiler pass, not by the
  program (the TPU compiler expands a scatter into a sort and a fusion,
  and adds copies, without metadata): it takes the layer and phase of the
  ops around it on the device when the nearest op with a path before it
  and the nearest after it agree, and no layer otherwise;
* only ops inside complete ``qmc.subblock`` spans of the window count, and
  the layer times are per generation (spans x ``steps``);
* ``unscoped_share``: per cent of the device busy time in those sub-blocks
  spent in ops with no layer;
* ``inherited_share``: per cent of that busy time spent in ops with no
  path that took their layer or phase from their neighbours, the part of
  the layer times that rests on that rule;
* ``idle_unattributed_share``: per cent of the device idle time in the
  whole traced window (``bench.window``) that no ``qmc.`` host span, on any
  thread, covers.  The window's head and tail count like any other gap: a
  span already open when the profiler started is not in the trace, so the
  host's work in the head is unattributed unless a later span covers it.

``of(ctx)`` is what the metric readers call: it reduces the run's trace
once per run.  A trace whose host has no ``qmc.`` spans reduces to None (a
program without them).  One whose device ops carry no ``qmc.`` scope at all (a
stale executable from the compile cache, or scopes missing) reports no
device-scope metric, and says why on stderr.
"""
from __future__ import annotations

import functools
import re
import sys
from collections import defaultdict
from pathlib import Path

from bench import load_module

HERE = Path(__file__).resolve().parent
_trace = load_module(HERE / 'trace.py')
union, clip, CONTAINERS = _trace.union, _trace.clip, _trace.CONTAINERS

PREFIX = 'qmc.'
SUBBLOCK = 'qmc.subblock'
WINDOW = _trace.WINDOW_SPAN
PHASE = 'block_stats'
LAYERS = ('ao_eval', 'ao_index', 'mo_product', 'slater', 'jastrow_energy',
          'sem_move', 'reconfig')
OP_LINES = _trace.OP_LINES
_SCOPE = re.compile(r'qmc\.(\w+)')

# -- the XSpace proto, the fields read here --------------------------------
# (tsl/profiler/protobuf/xplane.proto; numbers as there, maps as repeated
# key/value entries, which is their wire form)
_FIELDS = {
    'XSpace': [('planes', 1, 'XPlane', True)],
    'XPlane': [('id', 1, 'int64', False), ('name', 2, 'string', False),
               ('lines', 3, 'XLine', True),
               ('event_metadata', 4, 'EventMetadataEntry', True),
               ('stat_metadata', 5, 'StatMetadataEntry', True)],
    'EventMetadataEntry': [('key', 1, 'int64', False),
                           ('value', 2, 'XEventMetadata', False)],
    'StatMetadataEntry': [('key', 1, 'int64', False),
                          ('value', 2, 'XStatMetadata', False)],
    'XLine': [('id', 1, 'int64', False), ('name', 2, 'string', False),
              ('timestamp_ns', 3, 'int64', False),
              ('events', 4, 'XEvent', True)],
    'XEvent': [('metadata_id', 1, 'int64', False),
               ('offset_ps', 2, 'int64', False),
               ('duration_ps', 3, 'int64', False)],
    'XStat': [('metadata_id', 1, 'int64', False),
              ('str_value', 5, 'string', False),
              ('ref_value', 7, 'uint64', False)],
    'XEventMetadata': [('id', 1, 'int64', False), ('name', 2, 'string', False),
                       ('stats', 5, 'XStat', True)],
    'XStatMetadata': [('id', 1, 'int64', False), ('name', 2, 'string', False)],
}


@functools.cache
def _xspace():
    """The XSpace message class, built from ``_FIELDS``."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    scalar = {'int64': F.TYPE_INT64, 'uint64': F.TYPE_UINT64,
              'string': F.TYPE_STRING}
    fd = descriptor_pb2.FileDescriptorProto(
        name='qmcbench_xplane.proto', package='qmcbench', syntax='proto3')
    for msg, fields in _FIELDS.items():
        m = fd.message_type.add(name=msg)
        for name, number, typ, repeated in fields:
            f = m.field.add(name=name, number=number,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if typ in scalar:
                f.type = scalar[typ]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, f'.qmcbench.{typ}'
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName('qmcbench.XSpace'))


def _tf_op(meta, stat_names: dict, tf_op_id) -> str:
    for st in meta.stats:
        if st.metadata_id == tf_op_id:
            return st.str_value or stat_names.get(st.ref_value, '')
    return ''


def load(path):
    """-> (devices: {plane: [(name, tf_op, start, end)]},
    spans: [(name, start, end)]) in nanoseconds on the profiler's clock."""
    space = _xspace().FromString(Path(path).read_bytes())
    devices, spans = {}, []
    for plane in space.planes:
        names = {e.key: e.value for e in plane.event_metadata}
        if plane.name.startswith('/device:'):
            stat_names = {e.key: e.value.name for e in plane.stat_metadata}
            tf_op_id = next((k for k, v in stat_names.items()
                             if v == 'tf_op'), None)
            ops, cache = [], {}
            for line in plane.lines:
                if line.name not in OP_LINES:
                    continue
                for ev in line.events:
                    mid = ev.metadata_id
                    if mid not in cache:
                        meta = names.get(mid)
                        cache[mid] = (
                            meta.name.split(' = ')[0] if meta else '',
                            _tf_op(meta, stat_names, tf_op_id)
                            if meta else '')
                    start = line.timestamp_ns + ev.offset_ps / 1e3
                    ops.append(cache[mid] + (start,
                                             start + ev.duration_ps / 1e3))
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                for ev in line.events:
                    meta = names.get(ev.metadata_id)
                    name = meta.name if meta else ''
                    if name.startswith(PREFIX) or name == WINDOW:
                        start = line.timestamp_ns + ev.offset_ps / 1e3
                        spans.append((name, start,
                                      start + ev.duration_ps / 1e3))
    return devices, spans


def layer_of(tf_op: str):
    """-> (innermost layer or None, whether ``qmc.block_stats`` is on the
    path).  Transforms wrap scope names (``vmap(qmc.slater)``)."""
    found = _SCOPE.findall(tf_op)
    layers = [s for s in found if s != PHASE]
    return (layers[-1] if layers else None), PHASE in found


def _attribute(ops):
    """(layer, in_phase) of each op of one device, in the order given.

    Ops with a path are named by ``layer_of``; an op with none (made by a
    compiler pass) takes the name its neighbours in time agree on.
    """
    own = [None if n.startswith(CONTAINERS) or not op else layer_of(op)
           for n, op, _, _ in ops]
    order = sorted(range(len(ops)), key=lambda i: ops[i][2])
    before, after = {}, {}
    for seq, side in ((order, before), (order[::-1], after)):
        last = None
        for i in seq:
            if own[i] is not None:
                last = own[i]
            elif ops[i][1] == '':
                side[i] = last
    out = []
    for i, (_, op, _, _) in enumerate(ops):
        if op:
            out.append(layer_of(op))
        elif before.get(i) is not None and before.get(i) == after.get(i):
            out.append(before[i])
        else:
            out.append((None, False))
    return out


def _minus(intervals, cover):
    """The parts of sorted disjoint ``intervals`` outside sorted disjoint
    ``cover``."""
    out, j = [], 0
    for s, e in intervals:
        while j < len(cover) and cover[j][1] <= s:
            j += 1
        k = j
        while s < e and k < len(cover) and cover[k][0] < e:
            if cover[k][0] > s:
                out.append((s, cover[k][0]))
            s = max(s, cover[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def reduce(devices: dict, spans: list, steps: int):
    """Per-layer device ms per generation, shares and idle attribution.

    None when the host has no ``qmc.`` span or the device no op.
    """
    host = [(n, s, e) for n, s, e in spans if n.startswith(PREFIX)]
    if not host or not devices:
        return None
    win = [(s, e) for n, s, e in spans if n == WINDOW]
    t0, t1 = win[0] if win else (min(s for _, s, _ in host),
                                 max(e for _, _, e in host))
    subs = sorted((s, e) for n, s, e in host
                  if n == SUBBLOCK and t0 <= s and e <= t1)
    covered = union([(s, e) for _, s, e in host])
    n_dev = len(devices)
    layer_ns, phase_ns = defaultdict(float), 0.0
    busy_ns = idle_ns = unattributed_ns = inherited_ns = 0.0
    scoped = False
    for ops in devices.values():
        merged = clip(union([(s, e) for _, _, s, e in ops]), t0, t1)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        idle_ns += sum(e - s for s, e in idle)
        unattributed_ns += sum(e - s for s, e in _minus(idle, covered))
        inside = [(o, named) for o, named in zip(ops, _attribute(ops))
                  if any(a <= 0.5 * (o[2] + o[3]) < b for a, b in subs)]
        busy_ns += sum(e - s for s, e in union(
            [(o[2], o[3]) for o, _ in inside]))
        for (name, op, s, e), (layer, in_phase) in inside:
            if name.startswith(CONTAINERS):
                continue
            scoped = scoped or layer is not None or in_phase
            layer_ns[layer] += e - s
            if in_phase:
                phase_ns += e - s
            if not op and (layer is not None or in_phase):
                inherited_ns += e - s
    out = dict(
        subblocks=len(subs), generations=len(subs) * steps,
        idle_unattributed_share=(100.0 * unattributed_ns / idle_ns
                                 if idle_ns > 0 else 0.0))
    if not subs:
        print('scopes: no complete qmc.subblock span in the traced window',
              file=sys.stderr)
        return out
    if not scoped:
        print('scopes: no device op in the traced sub-blocks carries a '
              'qmc. scope: the executable predates the scopes (a stale '
              'compile-cache entry) or the scopes are missing',
              file=sys.stderr)
        return out
    per_gen = 1e-6 / (n_dev * out['generations'])
    busy = busy_ns * per_gen
    out.update(
        busy_ms=busy,
        layer_ms={k: layer_ns.get(k, 0.0) * per_gen for k in LAYERS},
        unscoped_ms=layer_ns.get(None, 0.0) * per_gen,
        unscoped_share=100.0 * layer_ns.get(None, 0.0) * per_gen / busy
        if busy > 0 else 0.0,
        inherited_ms=inherited_ns * per_gen,
        inherited_share=100.0 * inherited_ns * per_gen / busy
        if busy > 0 else 0.0,
        block_stats_ms=phase_ns * 1e-6 / (n_dev * len(subs)))
    return out


def reduce_file(path, steps: int):
    """``reduce(*load(path), steps)``."""
    return reduce(*load(path), steps=steps)


def of(ctx: dict) -> dict:
    """The reduction of this run's trace, made once and kept in ``ctx``.

    ``run.py`` hands a metric reader only ``ctx``, which holds neither the
    trace file nor the cell's generations per sub-block: both are locals
    (``found``, ``steps``) of ``run.run_cell``, the frame that calls the
    readers, and are read from it here.  {} where the run wrote no trace
    file or the program writes no ``qmc.`` span: a reader then reports
    nothing.  Raises LookupError where no ``run_cell`` frame holds those
    locals, so that a change to the harness fails the run loudly instead of
    silencing every metric read here.
    """
    if 'scopes' not in ctx:
        ctx['scopes'] = _from_run_cell()
    return ctx['scopes'] or {}


def _from_run_cell():
    frame = sys._getframe(1)
    while frame is not None and frame.f_code.co_name != 'run_cell':
        frame = frame.f_back
    local = frame.f_locals if frame is not None else {}
    if 'found' not in local or 'steps' not in local:
        raise LookupError('scopes: no run_cell frame with the locals found '
                          'and steps calls this reader')
    if not local['found']:
        print('scopes: the run wrote no trace file', file=sys.stderr)
        return None
    return reduce_file(local['found'][0], local['steps'])
