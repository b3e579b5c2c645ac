"""OpenAI-compatible LLM serving.

One deployment serves both the native protocol and the OpenAI request
shapes (`/v1/completions`, `/v1/chat/completions`) — point any OpenAI SDK
at the proxy URL. The engine underneath is the continuous-batching decode
engine (`ray_tpu/serve/llm.py`); `decode_chunk` amortizes per-token host
round trips.

Run: JAX_PLATFORMS=cpu python examples/11_openai_serving.py
"""

import json
import urllib.request

import jax
import jax.numpy as jnp

import ray_tpu as rt
from ray_tpu import serve
from ray_tpu.models import TransformerConfig, init_params
from ray_tpu.serve.openai_compat import OpenAICompatLLMServer


class CharTokenizer:
    """Toy tokenizer (1 char = 1 id) standing in for a real one — anything
    with encode/decode (e.g. a HuggingFace tokenizer) plugs in the same way."""

    def encode(self, s):
        return [ord(c) % 80 + 1 for c in s]

    def decode(self, ids):
        return "".join(chr((i - 1) % 26 + 97) for i in ids)


def main():
    rt.init(num_cpus=4)
    serve.start(http_port=0)
    try:
        cfg = TransformerConfig(
            vocab_size=89, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=64, attention="dense", dtype=jnp.float32,
        )
        params = init_params(cfg, jax.random.key(7))
        app = serve.deployment(OpenAICompatLLMServer).bind(
            lambda: (cfg, params, CharTokenizer()),
            max_batch_size=4, max_seq_len=64, decode_chunk=4,
        )
        serve.run(app, route_prefix="/v1")
        base = serve.proxy_url() + "/v1"

        def post(path, body):
            req = urllib.request.Request(
                base + path, data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"},
            )
            return urllib.request.urlopen(req, timeout=60)

        # completions
        resp = json.loads(post("/completions", {
            "model": "tiny", "prompt": "hello", "max_tokens": 6,
        }).read())
        assert resp["object"] == "text_completion"
        assert resp["usage"]["completion_tokens"] == 6

        # chat + streaming chunks over SSE
        stream = post("/chat/completions", {
            "model": "tiny", "max_tokens": 5, "stream": True,
            "messages": [{"role": "user", "content": "hi there"}],
        })
        chunks = [json.loads(l.decode()[6:]) for l in stream
                  if l.decode().startswith("data: ")]
        assert chunks[-1]["choices"][0]["finish_reason"] == "length"
        pieces = [c["choices"][0]["delta"].get("content", "") for c in chunks[:-1]]
        assert len(pieces) == 5
    finally:
        serve.shutdown()
        rt.shutdown()
    print("openai serving tour OK")


if __name__ == "__main__":
    main()
