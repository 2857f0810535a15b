"""Jit names of the programs each pipeline stage runs, and its ops
(shared by the per-tile device-time readers)."""

SEGMENTATION = {
    "programs": ("_rbc_accel", "_morph_open_accel", "_recon_accel",
                 "morph_recon", "_area_threshold_accel", "_fill_holes_accel",
                 "_pre_watershed_accel", "_watershed_accel", "_bwlabel_accel"),
    "ops": ("rbc_detection", "morph_open", "recon_to_nuclei",
            "area_threshold", "fill_holes", "pre_watershed", "watershed",
            "bwlabel"),
}
FEATURES = {
    "programs": ("_deconv_j", "color_deconv", "feature_fused",
                 "_pixel_stats_j", "_gradient_stats_j", "_haralick_j",
                 "_canny_j", "_morphometry_j"),
    "ops": ("color_deconv", "feature_fused", "pixel_stats", "gradient_stats",
            "haralick", "canny_edge", "morphometry"),
}


def device_s_per_tile(run, stage):
    """Device seconds of ``stage``'s programs in the traced window over
    the tiles' worth of its ops that ran there (runs of the stage's ops
    that ran at all, averaged): ``None`` when nothing of it ran."""
    if run.trace is None:
        return None
    groups = {"segmentation": SEGMENTATION["programs"],
              "features": FEATURES["programs"]}
    ops = SEGMENTATION["ops"] if stage == "segmentation" else FEATURES["ops"]
    kind = run.config["variants"]["accel_kind"]
    runs = run.runs()
    counts = [runs[f"{op}/{kind}"] for op in ops if runs.get(f"{op}/{kind}")]
    seconds = run.trace.program_seconds(groups)[stage]
    if not counts or seconds <= 0:
        return None
    return seconds / (sum(counts) / len(counts))


def kernel_share(run, op, program, work):
    """Percent of the roofline: least time of the window's ``op`` calls
    over the device time of the Pallas kernels inside programs named
    like ``program``; ``None`` when the kernel did not run."""
    if run.trace is None or run.peaks is None:
        return None
    calls = run.runs().get(f"{op}/{run.config['variants']['accel_kind']}", 0)
    seconds = run.trace.kernel_seconds(program)
    if not calls or seconds <= 0:
        return None
    from bench.roofline import least_time

    flops, nbytes = work(run.side)
    least, _ = least_time(flops * calls, nbytes * calls, run.peaks)
    return 100.0 * least / seconds
