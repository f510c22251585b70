"""Share of the frames run that are real: over the window's epoch
dispatches (the last ``window.units`` dispatch records of the program's
``repro.obs``), the live positions (the real lengths of the plans' live
utterances) over the positions run (steps x batch x the padded length).
The rest is padding: padded frames of live utterances, and padding rows.

Set-up's dispatches (the check's steps, mostly padding rows) come before
the window's and are not read.  Silent where the program keeps no
records or its plans carried no counts."""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    records = obs.dispatches()[-run.window.units:]
    if len(records) < run.window.units or any(
            r.positions is None for r in records):
        return None
    return (100.0 * sum(r.live_positions for r in records)
            / sum(r.positions for r in records))
