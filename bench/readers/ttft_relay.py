"""What of the client's mean time to first token (from the send, through the
router) lies in neither of the engine's own stretches: the way from the
client into the router, and the first event's way back from the engine's
first write through the router's relay.  ``router_added_ttft`` (the client's
mean minus the engine's ``tpu:ttft_seconds`` mean, handler -> first token)
minus the engine's means of ``tpu:request_upstream_seconds`` (router ->
handler) and ``tpu:first_token_write_seconds`` (first token -> first write).
None where the program has no such family, as before it stamped a request
where it arrives."""

from readers import prom_ratio, router_added_ttft

FAMILIES = ("tpu:request_upstream_seconds", "tpu:first_token_write_seconds")


def read(ctx, args):
    parts = [router_added_ttft.read(ctx, args)] + [
        prom_ratio.read(ctx, {"num": family + "_sum",
                              "den": family + "_count", "scale": 1e3})
        for family in FAMILIES]
    if None in parts:
        return None
    return parts[0] - sum(parts[1:])
