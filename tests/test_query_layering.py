"""The query path's arrows point one way (http -> engine -> planner ->
device program), and a query's cost record has one owner: structure
held by reading the source, no query run."""

import inspect
import os
import pathlib
import re
import subprocess
import sys

import pytest

from m3_tpu.query import cost as qcost
from m3_tpu.query import plan as qplan
from m3_tpu.query.engine import Engine
from m3_tpu.query.graphite import GraphiteEngine

REPO = pathlib.Path(__file__).resolve().parent.parent
IMPORTS_ENGINE = re.compile(
    r"from m3_tpu\.query\.engine import|from m3_tpu\.query import engine"
    r"|import m3_tpu\.query\.engine")


@pytest.mark.parametrize("module", ["m3_tpu.query.plan", "m3_tpu.query.cost"])
def test_importing_the_planner_or_the_cost_leaves_the_engine_out(module):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         f"import sys, {module}; "
         "print('m3_tpu.query.engine' in sys.modules, "
         "'m3_tpu.query.plan' in sys.modules)"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    engine_in, plan_in = out.stdout.split()
    assert engine_in == "False"
    assert plan_in == str(module.endswith("plan"))


@pytest.mark.parametrize("path", [
    "m3_tpu/query/plan.py", "m3_tpu/query/cost.py",
    "m3_tpu/query/matrix.py", "m3_tpu/serving/scheduler.py"])
def test_no_line_below_the_engine_imports_it(path):
    hits = [line for line in (REPO / path).read_text().splitlines()
            if IMPORTS_ENGINE.search(line)]
    assert hits == []


def test_the_record_copies_its_stats_through_the_table_alone():
    body = inspect.getsource(qcost.record)
    assert "stats.get(" not in body
    assert "STATS_FIELDS.items()" in body


def test_every_copied_field_is_in_the_operator_guide():
    guide = (REPO / "docs" / "observability.md").read_text()
    assert "query/cost.py" in guide
    missing = [name for name in qcost.STATS_FIELDS
               if f"`{name}`" not in guide]
    assert missing == []


@pytest.mark.parametrize("caller", [Engine._device_run, qplan.run_sym],
                         ids=["per_node", "fused"])
def test_both_device_tiers_describe_their_program_by_one_function(caller):
    body = inspect.getsource(caller)
    assert body.count("qcost.program_shape(") == 1
    for mine in ("decode_refills", "merge_form", "window_form"):
        assert mine not in body, mine


@pytest.mark.parametrize("caller", [Engine.query_range_with_meta,
                                    GraphiteEngine.render],
                         ids=["promql", "graphite"])
def test_one_per_query_scope(caller):
    body = inspect.getsource(caller)
    assert body.count("_query_scope(") == 1
    for its in ("_begin_cost", "gather_cache", "plan_cache", "sink_scope",
                "cache_stats"):
        assert its not in body, its
