"""prefill_cross_skip_pct.serve_tps: of the window's prompt tokens, the share the cross-decoder did NOT run on: 100 x (1 - prefill_cross_tokens / prefill_tokens), the model's device counter over the engine's in the final record. One token a prompt runs it (the finish) while the prefill stops at the self-decoder; 0 if a change runs the cross-decoder on every prompt token."""


def read(ctx):
    final = ctx.get("final", {})
    ran, tokens = final.get("prefill_cross_tokens"), final.get("prefill_tokens")
    if not isinstance(ran, int) or not tokens:
        return None  # a program without the counter
    print(f"the cross-decoder ran on {ran} of {tokens} prompt tokens ({final.get('admitted')} admitted)", flush=True)
    return 100.0 * (1.0 - ran / tokens)
