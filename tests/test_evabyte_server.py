"""tools/run_text_generation_server.py on a model with EVA attention, ids in
and ids out, as a subprocess on the CPU at a tiny size."""
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestServer:
    def test_the_tool_serves_an_eva_model_ids_in_ids_out(self):
        """tools/run_text_generation_server.py on a small stand-in (the
        smallest preset, EVA turned on by flags): the banner names the
        window, the chunk and what the engine refuses; a request that
        closes a window completes; GET /stats has the counters."""
        import signal
        import socket
        import subprocess
        import sys
        import time
        import urllib.request

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        proc = subprocess.Popen(
            [sys.executable,
             os.path.join(ROOT, "tools", "run_text_generation_server.py"),
             "--preset", "gpt2-125m",
             "--engine", "dynamic",
             "--kv-block-size", "4", "--eva-window-size", "32",
             "--eva-chunk-size", "4", "--prefill-chunk", "8",
             "--max-seq-len", "96", "--max-batch", "2",
             "--host", "127.0.0.1", "--port", str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu",
                               PYTHONPATH=ROOT))

        def http(path, body=None):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}{path}",
                data=None if body is None else json.dumps(body).encode(),
                method="GET" if body is None else "PUT",
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as resp:
                return json.loads(resp.read())

        try:
            t0 = time.perf_counter()
            while True:
                assert proc.poll() is None, proc.stdout.read()[-3000:]
                try:
                    http("/healthz")
                    break
                except OSError:
                    assert time.perf_counter() - t0 < 300
                    time.sleep(0.5)
            out = http("/api", {
                "prompts": [" ".join(str(7 + 3 * i) for i in range(27))],
                "tokens_to_generate": 12, "greedy": True})
            assert len(out["segments"][0].split()) == 12
            stats = http("/stats")
        finally:
            proc.send_signal(signal.SIGINT)
            try:
                banner = proc.communicate(timeout=30)[0]
            except subprocess.TimeoutExpired:
                proc.kill()
                banner = proc.communicate()[0]
        # 27 + 12 - 1 = 38 cached rows: the window of 32 closed in decode
        assert stats["eva"]["windows_closed"] == 1
        assert stats["eva"]["window"] == 32 and stats["eva"]["chunk"] == 4
        assert stats["pool"]["blocks_in_use"] == 0
        for word in ("eva=window 32 exact rows", "every 4 older",
                     "refused on chunk summaries"):
            assert word in banner, banner[-2000:]

    def test_the_tool_refuses_the_static_engine(self):
        import subprocess
        import sys
        out = subprocess.run(
            [sys.executable,
             os.path.join(ROOT, "tools", "run_text_generation_server.py"),
             "--preset", "evabyte-6.5b"], capture_output=True, text=True,
            cwd=ROOT, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT))
        assert out.returncode != 0
        assert "chunk summaries" in out.stderr
        assert "--engine dynamic" in out.stderr
