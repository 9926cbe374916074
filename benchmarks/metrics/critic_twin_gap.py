"""How far the two target critics lie apart where the clipped double-Q target
takes their minimum: the batch mean of |Q'_1 - Q'_2| at the smoothed target
action (`td3_twin_gap` on each `"train"` record: the newest chunk's last
update), mean over the window's records, in units of return. It says how much
the minimum bites and moves no rate. Only a program with twin critics writes
the key."""


def read(run):
    gaps = [r["td3_twin_gap"] for r in run["window"] if "td3_twin_gap" in r]
    return sum(gaps) / len(gaps) if gaps else None
