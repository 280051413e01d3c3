"""held_route_share (%): of the (decode token, MoE layer, top-k pick)
triples of the steps due in the window, the share that fell on an expert
this chip holds, by the engine's count on each request
(``held_routes``).  A request's decode tokens are its output tokens less
the first, which its prefill produced.  12.5% is the share of 8 experts
of 64 under uniform routing.  None where the engine counts no routes."""


def read(run):
    spec = run.cell.spec
    per_token = ((spec["num_hidden_layers"] - spec["first_k_dense_replace"])
                 * spec["num_experts_per_tok"])
    held = picks = 0
    for s in run.rec.steps:
        if not s.counted or s.req is None:
            continue
        if not hasattr(s.req, "held_routes"):
            return None
        held += s.req.held_routes
        picks += max(len(s.req.tokens) - s.req.prompt_len - 1, 0) * per_token
    return 100.0 * held / picks if picks else None
