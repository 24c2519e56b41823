"""Why is one run in three of a serving cell some 5% slow from start to end?

Runs one cell several times in one call, each run a new process, under the
arms given on the command line, and prints for each run the host's side of
it: which CPUs its busiest threads ran on, its memory per NUMA node, and
the run's own summary lines. The parent never touches JAX.

    python3 benchmark/tools/slowmode.py <workload> <seconds> <seed> arm [arm ...]

An arm is ``name[:KEY=VALUE,...][@cpulist]``: environment variables for the
child, and a CPU list for ``taskset``-like pinning (``os.sched_setaffinity``
in the child before it starts).
"""
import glob
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HERE)
OUT = os.path.join(REPO, "chiprun_out", "slowmode")


def topology():
    print("cpu_count", os.cpu_count(), "affinity",
          sorted(os.sched_getaffinity(0)))
    for node in sorted(glob.glob("/sys/devices/system/node/node*")):
        try:
            print(os.path.basename(node), "cpus",
                  open(node + "/cpulist").read().strip())
        except OSError as e:
            print(node, e)
    for f in ("/proc/self/status",):
        for line in open(f):
            if line.startswith(("Cpus_allowed_list", "Mems_allowed_list")):
                print(line.strip())
    try:
        out = subprocess.run(["lscpu"], capture_output=True, text=True).stdout
        print("\n".join(l for l in out.splitlines() if any(
            k in l for k in ("Model name", "Socket", "NUMA", "Thread", "Core",
                             "MHz", "Hypervisor", "L3"))))
    except OSError as e:
        print("lscpu:", e)
    print("loadavg", open("/proc/loadavg").read().strip())


def threads(pid):
    out = {}
    for d in glob.glob(f"/proc/{pid}/task/*"):
        try:
            raw = open(d + "/stat").read()
        except OSError:
            continue
        comm = raw[raw.index("(") + 1:raw.rindex(")")]
        f = raw[raw.rindex(")") + 2:].split()
        out[os.path.basename(d)] = (comm, int(f[11]) + int(f[12]), int(f[36]))
    return out


def numa(pid):
    tot = {}
    try:
        for line in open(f"/proc/{pid}/numa_maps"):
            for tok in line.split():
                if tok[0] == "N" and "=" in tok and tok[1:tok.index("=")].isdigit():
                    k, v = tok.split("=")
                    tot[k] = tot.get(k, 0) + int(v)
    except OSError as e:
        return str(e)
    return tot


def run(workload, seconds, seed, arm, k):
    name, _, cpus = arm.partition("@")
    name, _, envs = name.partition(":")
    env = dict(os.environ)
    env.pop("PYTHONHASHSEED", None)
    for kv in filter(None, envs.split(",")):
        key, _, val = kv.partition("=")
        env[key] = val
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
           workload, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", "0"] + (
        ["--rehearse"] if os.environ.get("SLOWMODE_REHEARSE") else [])
    pre = None
    if cpus:
        want = set()
        for part in cpus.split(","):
            a, _, b = part.partition("-")
            want.update(range(int(a), int(b or a) + 1))
        pre = lambda: os.sched_setaffinity(0, want)     # noqa: E731
    log = os.path.join(OUT, f"{k:02d}_{name}.log")
    t0 = time.time()
    with open(log, "w") as f:
        p = subprocess.Popen(cmd, stdout=f, stderr=subprocess.STDOUT, env=env,
                             cwd=REPO, preexec_fn=pre)
        cpus_seen, last, nm = {}, {}, None
        while p.poll() is None:
            time.sleep(1.0)
            th = threads(p.pid)
            for tid, (comm, ticks, cpu) in th.items():
                cpus_seen.setdefault(tid, {}).setdefault(cpu, 0)
                cpus_seen[tid][cpu] += 1
            last.update(th)
            nm = numa(p.pid) or nm
    top = sorted(last.items(), key=lambda kv: -kv[1][1])[:6]
    lines = [l for l in open(log).read().splitlines()]
    keep = [l for l in lines if "tpot ms" in l or "window closed" in l
            or "output tokens" in l]
    res = {}
    for l in reversed(lines):
        if l.startswith("{"):
            res = {k2: v["value"] for k2, v in json.loads(l).get(
                "metrics", {}).items()}
            break
    print(f"== run {k} arm {arm} rc {p.returncode} wall {time.time()-t0:.0f} s "
          f"loadavg {open('/proc/loadavg').read().split()[0]}")
    print("   metrics", json.dumps(res))
    for l in keep:
        print("   " + l[:300])
    print("   numa pages", nm)
    for tid, (comm, ticks, cpu) in top:
        print(f"   thread {tid} {comm!r} cpu-ticks {ticks} on cpus "
              f"{dict(sorted(cpus_seen.get(tid, {}).items()))}")
    sys.stdout.flush()


def main():
    workload, seconds, seed = sys.argv[1], sys.argv[2], int(sys.argv[3])
    os.makedirs(OUT, exist_ok=True)
    topology()
    for k, arm in enumerate(sys.argv[4:]):
        run(workload, seconds, seed, arm, k)


if __name__ == "__main__":
    main()
