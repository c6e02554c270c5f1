"""Set-up's data: tables from the seed, parquet in the checkout."""

import json
import os
import shutil

import pyarrow.parquet as pq

from benchmark.harness.spec import BENCH

CACHE = os.path.join(BENCH, ".cache")


def cache_dir(*parts):
    d = os.path.join(CACHE, *parts)
    os.makedirs(d, exist_ok=True)
    return d


def write_parquet(tables, config, seed, sf):
    """One directory per table under ``.cache/data/<config>-sf<sf>-<seed>/``,
    each table in as many files of equal rows as the configuration's
    ``storage.files`` says (1 where it says nothing): the number of files
    is the configuration's, never the seed's.  Reused when a complete
    copy is there (a stamp is written last).  Returns ({table:
    directory}, tables written)."""
    files = config["storage"].get("files", {})
    tag = f"{config['name']}-sf{sf:g}-{seed}"
    base = os.path.join(cache_dir("data"), tag)
    dirs, wrote = {}, 0
    for name, table in tables.items():
        d = os.path.join(base, name)
        # beside the directory, not in it: the engine reads every file
        stamp = os.path.join(base, name + ".complete.json")
        dirs[name] = d
        n_files = int(files.get(name, 1))
        want = {"rows": table.num_rows, "files": n_files}
        if os.path.exists(stamp):
            with open(stamp) as f:
                if json.load(f) == want:
                    continue
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
        if os.path.exists(stamp):
            os.remove(stamp)
        per_file = -(-table.num_rows // n_files)
        for i in range(n_files):
            pq.write_table(table.slice(i * per_file, per_file),
                           os.path.join(d, f"part-{i:03d}.parquet"))
        with open(stamp, "w") as f:
            json.dump(want, f)
        wrote += 1
    return dirs, wrote


def prune(keep_tag_prefix, keep=8):
    """Keep the checkout's data cache small: at most ``keep`` seeds of a
    configuration stay (oldest go first)."""
    root = cache_dir("data")
    mine = sorted((os.path.join(root, d) for d in os.listdir(root)
                   if d.startswith(keep_tag_prefix)),
                  key=os.path.getmtime)
    for d in mine[:-keep]:
        shutil.rmtree(d, ignore_errors=True)
