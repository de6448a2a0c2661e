"""Admission and batching: ``tpot_p95_ms``'s own quantity taken where the
engine's work for a token ends, the landing of its dispatch.  Per request
due in the window: (the landing of its last block - the landing of its
first) / (``generated_tokens`` - 1), from the ``engine.decode`` span's
``last_landed_ms`` and ``first_landed_ms``; 95th percentile.  No consumer is
inside it: what ``tpot_p95_ms`` holds beyond it is the stream's road."""

from benchmarks.readers._stream import p95_per_request


def read(ctx):
    def per_token(s):
        n = s.attrs.get("generated_tokens", 0)
        return (s.attrs["last_landed_ms"] - s.attrs["first_landed_ms"]) / (n - 1) if n > 1 else None

    return p95_per_request(ctx, "engine_tpot_landed_p95_ms", per_token)
