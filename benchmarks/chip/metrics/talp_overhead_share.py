"""TALP's own cost, in % of the monitored loop: the seconds of the
monitor's ``talp_overhead`` annotation (a share of the Global region) over
the elapsed time of the loop region (``train_loop`` or ``decode``)."""


def read(rec):
    regions = rec["talp"]["regions"]
    glob = regions.get("Global") or {}
    host = glob.get("host_metrics") or {}
    share = host.get("talp_overhead")
    loop = regions.get(rec["loop_region"]) or {}
    if share is None or not loop.get("elapsed"):
        return None
    return float(share * glob["elapsed"] / loop["elapsed"] * 100.0)
