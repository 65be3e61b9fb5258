"""The runtime's own trace points: host spans and device scopes.

Host spans are ``jax.profiler.TraceAnnotation``s, so they land in the
profiler's trace beside the device ops, on the same clock.  Each carries
``wave=<id>``, the engine's wave id (``DelegationEngine.wave_counter``),
so every span of one wave reads the same id.  Outside a profiler trace a
span costs about a microsecond; the profiler keeps the spans itself and
writes them out at ``stop_trace``.

Device scopes are ``jax.named_scope``s: they name the round's ops in the
compiled program's ``op_name`` metadata (``.../trust.serve/kv.commit/...``)
and leave the program itself unchanged.  Every scope of the round goes
through ``scope`` below.

Host spans (parent, then its children):

  trust.submit    a typed submit or apply: trust.bind (the schema bind and
                  its copies to the device), trust.route (the router)
  trust.step      DelegationEngine.step: trust.build (a new program: build,
                  trace, compile and its first call) or trust.launch (the
                  cached program's call)
  trust.consume   StreamingDriver consuming a wave: trust.wait (blocked on
                  the device), trust.callback (the wave's on_consume)

Device scopes: trust.fuse (concat and pad of the queued batches),
trust.pack, trust.transmit, trust.serve, trust.respond (the channel), and
inside the KV store's serve kv.get, kv.put, kv.add, kv.cas, with kv.commit
around each write-back of the table.
"""
from __future__ import annotations

import jax

SUBMIT = "trust.submit"
BIND = "trust.bind"
ROUTE = "trust.route"
STEP = "trust.step"
BUILD = "trust.build"
LAUNCH = "trust.launch"
CONSUME = "trust.consume"
WAIT = "trust.wait"
CALLBACK = "trust.callback"

FUSE = "trust.fuse"
PACK = "trust.pack"
TRANSMIT = "trust.transmit"
SERVE = "trust.serve"
RESPOND = "trust.respond"
KV_GET = "kv.get"
KV_PUT = "kv.put"
KV_ADD = "kv.add"
KV_CAS = "kv.cas"
KV_COMMIT = "kv.commit"


def span(name: str, wave: int) -> jax.profiler.TraceAnnotation:
    """A host span of wave ``wave``, for a ``with`` statement."""
    return jax.profiler.TraceAnnotation(name, wave=wave)


def scope(name: str):
    """A device scope over the ops traced inside it (``with`` statement)."""
    return jax.named_scope(name)
