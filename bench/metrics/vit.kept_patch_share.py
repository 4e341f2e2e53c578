"""Share of the encoded frames' patches the vision tower ran, in %:
``WindowStats.vit_patches`` over every patch of the frames each window
encodes (the whole window when fresh, the new stride otherwise).
I-frames count whole; P-frames count their codec-kept patches."""


def read(run):
    g = run.geometry
    kept = total = 0
    for w in run.windows:
        fresh = w["tokens_refreshed"] >= g["total"]
        frames = g["frames_fresh"] if fresh else g["frames_inc"]
        kept += w["vit_patches"]
        total += frames * g["n_patches"]
    if not total:
        return None
    return 100.0 * kept / total
