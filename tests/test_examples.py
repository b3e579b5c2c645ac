"""The examples/ scripts stay runnable (parity model: the reference CIs its
doc examples). Each runs as a real subprocess — user-style, own interpreter,
CPU platform — and must exit 0."""

import os
import pathlib
import subprocess
import sys

import pytest

EXAMPLES = pathlib.Path(__file__).resolve().parent.parent / "examples"


def _run(name: str, timeout: int = 240) -> str:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(EXAMPLES.parent) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, str(EXAMPLES / name)],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    assert proc.returncode == 0, f"{name} failed:\n{proc.stdout}\n{proc.stderr}"
    return proc.stdout


def test_example_tasks_and_actors():
    assert "tasks/actors tour OK" in _run("01_tasks_and_actors.py")


def test_example_data_pipeline():
    assert "data tour OK" in _run("02_data_pipeline.py")


def test_example_train_transformer():
    assert "train tour OK" in _run("03_train_transformer.py")


def test_example_generation():
    assert "generation tour OK" in _run("07_generation.py")


@pytest.mark.full
def test_example_tune_search():
    assert "tune tour OK" in _run("04_tune_search.py")


@pytest.mark.full
def test_example_serve_deployment():
    assert "serve tour OK" in _run("05_serve_deployment.py")


@pytest.mark.full
def test_example_rllib_ppo():
    assert "rllib tour OK" in _run("06_rllib_ppo.py")


def test_example_workflows():
    assert "workflow tour OK" in _run("08_workflows.py")


@pytest.mark.full
def test_example_llm_serving():
    assert "llm tour OK" in _run("09_llm_serving.py")


def test_example_dask_graphs():
    assert "dask tour OK" in _run("10_dask_graphs.py")


@pytest.mark.full
def test_example_openai_serving():
    assert "openai serving tour OK" in _run("11_openai_serving.py")
