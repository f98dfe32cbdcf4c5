"""mtp_accept_pct.serve_tps: of the row-steps that verified a draft, the share whose draft was the main stack's own choice and was delivered with the step's second token (100 x mtp_accepted / mtp_drafts, the model's device counters in the final record). The harness's record holds no draft, so the reference's draft_agree_pct (the block followed teacher-forced over the checked requests, an extra key of check_out.json) is printed beside it."""
import json
from pathlib import Path

from benchmark.span_readers import final_value

ROOT = Path(__file__).resolve().parents[2]


def read(ctx):
    value = final_value("mtp_accept_pct")(ctx)
    if value is None:
        return None  # a program that drafts nothing
    final = ctx["final"]
    try:
        ref = json.loads((ROOT / ".benchrun" / ctx["cell"]["name"] / "check_out.json").read_text())
    except (OSError, ValueError):
        ref = {}
    print(f"drafts: mtp_accepted {final.get('mtp_accepted')} of mtp_drafts {final.get('mtp_drafts')} row-steps = "
          f"{value}%; decode_yield_pct {final.get('decode_yield_pct')} (tokens over row-steps); the reference's block, "
          f"teacher-forced: draft_agree_pct {ref.get('draft_agree_pct')} over {ref.get('draft_positions')} served tokens",
          flush=True)
    return value
