"""Layer: entry, training. Source: program_counter (mxtpu.telemetry
`fit_sync_wait_ms` + `fit_metric_sync_ms`). Share of the window in which
`Module.fit` sat waiting for the device: near 100% is a device-bound step."""


def read(facts):
    tel = facts.get("telemetry")
    if not tel:
        return None
    ms = tel["fit_sync_wait_ms"]["sum"] + tel["fit_metric_sync_ms"]["sum"]
    return 100.0 * ms / 1e3 / facts["window_s"]
