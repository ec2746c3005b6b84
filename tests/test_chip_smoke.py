"""CPU rehearsal of ``chip_smoke.py``: its phases run end to end at smoke
size (Pallas in interpret mode, so no kernel is compiled), and the script
refuses to report success anywhere but on a TPU."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np

import repro.configs as C

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_train_and_serve_phases_rehearse_on_cpu():
    cs = _load()
    cfg = C.get_smoke("gpt-moe-s")
    res, state, s = cs.train_phase(cfg, [
        "--arch", "gpt-moe-s", "--smoke", "--steps", "2",
        "--global-batch", "2", "--seq-len", "32"])
    cs.check_train(res)
    assert res["mesh"] == {"data": 1, "model": 1}
    assert len(res["losses"]) == 2 and np.isfinite(res["losses"]).all()
    assert res["dropped_frac"] == [0.0, 0.0]
    assert res["kernels"] == []             # interpret mode: no custom calls
    json.dumps(res)                         # the printed line is JSON
    res = cs.serve_phase(cfg, s.rt, state.params, impl="ring",
                         prompts=(8, 5, 7), new_tokens=3, page_size=4)
    cs.check_serve(res)
    assert res["generated"] == [3, 3, 3]


def test_four_chip_phase_rehearses_on_host_devices(dist):
    """Ring and ep agree on the first-step loss on a simulated (1, 4) mesh
    and drop no token."""
    out = dist(f"""
import importlib.util, json
import repro.configs as C
spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
cs = importlib.util.module_from_spec(spec); spec.loader.exec_module(cs)
res = cs.four_chip_phase(C.get_smoke("gpt-moe-s"), batch=2, seq=32)
cs.check_four_chips(res)
print("RESULT " + json.dumps(res))
""", n_devices=4)
    res = json.loads(out.split("RESULT ", 1)[1])
    assert res["mesh"] == {"data": 1, "model": 4}
    assert res["ring"]["dropped_frac"] == res["ep"]["dropped_frac"] == 0.0


def test_main_refuses_a_cpu_device(capsys):
    assert _load().main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails_without_success_line(tmp_path):
    shutil.copy(SCRIPT, tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
