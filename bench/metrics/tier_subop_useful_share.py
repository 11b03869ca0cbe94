"""Share of the tier fleets' scanned device sub-op slots that carry
device work.

Work is each returned tier cell's device ops: its trace ops sent to the
device (`host_dev_ops`), eviction write-backs (`host_evict_w`) and flush
writes (`host_flush_w`). Slots are every tier fleet's cells, pad cells
included, times the steps its scan ran (`t_scan`) times the sub-op slots
a step runs (`sub_ops`), from the `device.scan` spans whose `hostcache`
argument is set. The rest are pads: host-absorbed ops and idle eviction
and flush slots. A count: it repeats exactly for a seed. Nothing is read
where no tier fleet's `device.scan` span is in the window."""

WORK = ("host_dev_ops", "host_evict_w", "host_flush_w")


def read(run):
    slots = sum((a["cells"] + a["pad"]) * a["t_scan"] * a["sub_ops"]
                for a in (sp["args"] for sp in run.spans
                          if sp["name"] == "device.scan")
                if a.get("hostcache"))
    if not slots:
        return None
    work = sum(r[k] for it in run.window.iterations
               for r in it.results.values() if WORK[0] in r for k in WORK)
    return 100.0 * work / slots
