"""prefill_rounds_per_prompt.serve_tps: the engine's prefill_rounds (boundaries that queued chunks of a prompt) over admitted: 1 while every prompt fits a boundary's budget (serving/engine.py ADMIT_TOKENS), more where a long prompt's prefill is spread over several boundaries with decode dispatches between."""


def read(ctx):
    final = ctx.get("final", {})
    if not final.get("prefill_rounds") or not final.get("admitted"):
        return None  # a program without the counter
    print(f"prefill rounds {final['prefill_rounds']} for {final['admitted']} admitted ({final.get('prefill_chunks')} chunks, "
          f"{final.get('admit_rounds')} boundaries that queued a head)", flush=True)
    return final["prefill_rounds"] / final["admitted"]
