"""Of the admissions that found a decode sweep in flight, the share dispatched behind it with no drain (``kvmini_tpu_admissions_inflight_total``) rather than after retiring it (``kvmini_tpu_pipeline_fallback_total{reason="active_set"}``, which also counts a chunked prompt's last piece and a handoff's activation); an admission into an idle engine counts in neither. Nothing against a program without the first counter or a window without such an admission."""

DRAINED = 'kvmini_tpu_pipeline_fallback_total{reason="active_set"}'


def read(ctx):
    behind = ctx.counters.get("kvmini_tpu_admissions_inflight_total")
    if behind is None:
        return None
    found = behind + ctx.counters.get(DRAINED, 0.0)
    if found <= 0:
        return None
    return 100.0 * behind / found
