"""OpenAI-compatible request/response adapter over :class:`LLMServer`
(``serve/llm.py``): an HTTP-shaped translation that shares nothing with the
engine but the server it wraps."""

from __future__ import annotations

import time
from typing import Any, Dict, List

from ray_tpu.serve.llm import LLMServer


class OpenAICompatLLMServer(LLMServer):
    """OpenAI-compatible request/response adapter over :class:`LLMServer`.

    Accepts the body shapes of ``POST /v1/completions`` (``model`` +
    ``prompt``) and ``POST /v1/chat/completions`` (``model`` +
    ``messages``) and answers in the matching OpenAI response envelopes,
    including streaming chunk events over the proxy's SSE path.  Dispatch
    is by body shape — the HTTP proxy routes whole apps by path prefix, so
    one deployment serves both the native protocol and the OpenAI one.
    (Beyond reference parity: the reference delegates OpenAI-compatible
    LLM serving to vLLM.)

    Text prompts/messages need the model_factory to supply a tokenizer;
    token-id prompts work without one.  ``stop`` supports a single token id
    (honored in-engine as eos) or, with a tokenizer, a string trimmed from
    the non-streaming response.
    """

    def __call__(self, request: Any):
        if isinstance(request, dict) and ("messages" in request or "model" in request):
            return self._openai(request)
        return super().__call__(request)

    # ------------------------------------------------------------- openai
    def _openai(self, body: Dict[str, Any]):
        import uuid

        self._reject_unsupported(body)
        chat = "messages" in body
        prompt_ids = self._openai_prompt(body, chat)
        stop = body.get("stop")
        eos_id = None
        stop_text = None
        if isinstance(stop, int):
            eos_id = stop
        elif isinstance(stop, str):
            if self.tokenizer is not None:
                enc = self.tokenizer.encode(stop)
                if len(enc) == 1:
                    eos_id = enc[0]
                else:
                    stop_text = stop
            else:
                raise ValueError("string stop requires a tokenizer")
        elif isinstance(stop, list) and len(stop) == 1:
            return self._openai({**body, "stop": stop[0]})
        elif stop is not None:
            raise ValueError("stop: a single token id or string is supported")

        kw = dict(
            max_tokens=int(body.get("max_tokens", 16)),
            # OpenAI semantics: absent temperature means 1.0 (sampling) —
            # defaulting to greedy here would silently answer a different
            # distribution than every OpenAI SDK client expects
            temperature=float(body.get("temperature", 1.0)),
            eos_id=eos_id,
        )
        rid = ("chatcmpl-" if chat else "cmpl-") + uuid.uuid4().hex[:24]
        model = body.get("model", "ray_tpu")
        created = int(time.time())
        obj = "chat.completion" if chat else "text_completion"

        if body.get("stream"):
            if stop_text is not None:
                raise ValueError(
                    "streaming with a multi-token stop string is not "
                    "supported — use a stop that encodes to one token"
                )
            stream = self.engine.submit_stream(prompt_ids, **kw)

            def chunks():
                reason = "length"
                for tok in stream:
                    if eos_id is not None and tok == eos_id:
                        # OpenAI semantics: the stop sequence is excluded
                        # from the streamed output
                        reason = "stop"
                        continue  # engine ends the stream after eos
                    piece = (
                        self.tokenizer.decode([tok])
                        if self.tokenizer is not None
                        else None
                    )
                    delta = (
                        {"delta": {"content": piece}, "index": 0, "finish_reason": None}
                        if chat
                        else {"text": piece, "token_ids": [tok], "index": 0,
                              "finish_reason": None}
                    )
                    yield {"id": rid, "object": obj + ".chunk", "created": created,
                           "model": model, "choices": [delta]}
                final = (
                    {"delta": {}, "index": 0, "finish_reason": reason}
                    if chat
                    else {"text": "", "index": 0, "finish_reason": reason}
                )
                yield {"id": rid, "object": obj + ".chunk", "created": created,
                       "model": model, "choices": [final]}

            return chunks()

        out = self.engine.generate(prompt_ids, **kw)
        finish = "stop" if (eos_id is not None and out and out[-1] == eos_id) else "length"
        if finish == "stop":
            out = out[:-1]  # OpenAI semantics: stop sequence excluded
        text = self.tokenizer.decode(out) if self.tokenizer is not None else None
        if text is not None and stop_text and stop_text in text:
            # trim at TOKEN granularity so token_ids stay faithful to what
            # the model generated (re-encoding trimmed text could produce
            # ids the model never emitted): keep the longest generated
            # prefix whose decode does not yet contain the stop text, and
            # derive text from it so decode(token_ids) == text
            # contains-stop is monotone in the prefix length, so binary
            # search the cut (a linear scan would decode O(n) prefixes on
            # the serving hot path when the stop lands early)
            lo, hi = 0, len(out)  # invariant: decode(out[:lo]) lacks stop
            while lo < hi:
                mid = (lo + hi + 1) // 2
                if stop_text in self.tokenizer.decode(out[:mid]):
                    hi = mid - 1
                else:
                    lo = mid
            out = out[:lo]
            text = self.tokenizer.decode(out)
            finish = "stop"
        choice: Dict[str, Any] = {"index": 0, "finish_reason": finish, "token_ids": out}
        if chat:
            choice["message"] = {"role": "assistant", "content": text}
        else:
            choice["text"] = text
        return {
            "id": rid,
            "object": obj,
            "created": created,
            "model": model,
            "choices": [choice],
            "usage": {
                "prompt_tokens": len(prompt_ids),
                "completion_tokens": len(out),
                "total_tokens": len(prompt_ids) + len(out),
            },
        }

    def _reject_unsupported(self, body: Dict[str, Any]) -> None:
        """Unimplemented OpenAI sampling params fail loudly — silently
        ignoring them would return samples the client didn't ask for.
        Values matching OpenAI defaults (top_p=1, n=1, zero penalties)
        pass, since SDKs send those unprompted."""
        bad = []
        top_p = body.get("top_p")
        if top_p is not None and top_p < 1.0:
            # sampling config is per-ENGINE: a request may restate the
            # engine's own top_p, but asking for a different distribution
            # must not be silently overridden.  top_p=1.0 always passes —
            # SDKs send the OpenAI default unprompted.
            eng_p = self.engine.top_p
            if eng_p is None or abs(float(top_p) - float(eng_p)) > 1e-9:
                bad.append(
                    f"top_p={top_p} (engine is configured with "
                    f"top_p={eng_p}; per-request nucleus sampling is not "
                    "supported — configure it on the deployment)"
                )
        if body.get("n", 1) not in (None, 1):
            bad.append("n > 1")
        if body.get("best_of", 1) not in (None, 1):
            bad.append("best_of > 1")
        lp = body.get("logprobs")
        if lp is not None and lp is not False:  # NOT `in (None, False)`: 0 == False
            bad.append("logprobs")
        for k in ("presence_penalty", "frequency_penalty"):
            if body.get(k):
                bad.append(k)
        if body.get("echo"):
            bad.append("echo")
        if self.engine.cfg.block > 1:
            # generation by diffusion over blocks: a token comes from a
            # confidence schedule over several forwards of its block, not from
            # one next-token distribution, so nothing that rests on that
            # distribution can be honoured, now or by a later sampler: say so
            why = (f" (the model generates by diffusion over blocks of {self.engine.cfg.block}: "
                   "no next-token distribution a position)")
            bad = [b + why if b in ("logprobs", "n > 1", "best_of > 1") else b for b in bad]
            bad += [k + why for k in ("top_logprobs", "logit_bias") if body.get(k)]
        if bad:
            raise ValueError(
                "unsupported OpenAI parameter(s): " + ", ".join(bad)
            )

    def _openai_prompt(self, body: Dict[str, Any], chat: bool) -> List[int]:
        if chat:
            messages = body["messages"]
            if self.tokenizer is None:
                raise ValueError("chat completions require a tokenizer")
            template = getattr(self.tokenizer, "apply_chat_template", None)
            if template is not None:
                ids = template(messages, add_generation_prompt=True)
                return list(ids)
            joined = "\n".join(f"{m['role']}: {m['content']}" for m in messages)
            return list(self.tokenizer.encode(joined + "\nassistant:"))
        prompt = body.get("prompt")
        if isinstance(prompt, str):
            if self.tokenizer is None:
                raise ValueError("string prompts require a tokenizer")
            return list(self.tokenizer.encode(prompt))
        if isinstance(prompt, list) and all(isinstance(t, int) for t in prompt):
            return prompt
        raise ValueError("prompt must be a string or a list of token ids")
