"""LLM chat wrappers (reference: xpacks/llm/llms.py:43-771).

TPU-first: `JaxChat` runs the on-device decoder (models/decoder.py);
OpenAI/LiteLLM-compatible wrappers keep API parity for externally-hosted
models.  All chats are callable on column expressions and support the
`prompt_chat_single_qa` convention.
"""

from __future__ import annotations

from typing import Any

from ...internals import dtype as dt
from ...internals.expression import ApplyExpression, ColumnExpression


def prompt_chat_single_qa(question: str) -> list[dict]:
    return [{"role": "user", "content": question}]


class BaseChat:
    """Callable on expressions; subclasses implement _call_llm(messages)."""

    def _call_llm(self, messages: list[dict], **kwargs) -> str:
        raise NotImplementedError

    def _accepts_call_arg(self, arg_name: str) -> bool:
        return True

    def __call__(self, messages, **kwargs):
        if isinstance(messages, ColumnExpression):
            def fn(msgs):
                if isinstance(msgs, str):
                    msgs = prompt_chat_single_qa(msgs)
                elif hasattr(msgs, "value"):
                    msgs = msgs.value
                return self._call_llm(msgs, **kwargs)

            return ApplyExpression(fn, dt.STR, (messages,), {}, propagate_none=True)
        if isinstance(messages, str):
            messages = prompt_chat_single_qa(messages)
        return self._call_llm(messages, **kwargs)


class JaxChat(BaseChat):
    """On-device decoder LM (models/decoder.py) — generation without leaving
    the TPU.  Untrained weights generate token markers; load trained params
    via `params=` for real text."""

    def __init__(self, config=None, *, seed: int = 0, max_new_tokens: int = 64,
                 params=None, model: str | None = None, **kwargs):
        import os

        from ...models.decoder import DecoderConfig, JaxDecoderLM

        self.model_name = model or "pathway-tpu-decoder"
        if model is not None and config is None and os.path.exists(model):
            # a local checkpoint path = GPT-2-family HF weights on the TPU path
            self._lm = JaxDecoderLM.from_hf(model)
        else:
            self._lm = JaxDecoderLM(config or DecoderConfig(), seed=seed)
        if params is not None:
            self._lm.params = params
        self.max_new_tokens = max_new_tokens

    def _call_llm(self, messages: list[dict], **kwargs) -> str:
        prompt = "\n".join(f"{m.get('role', 'user')}: {m.get('content', '')}" for m in messages)
        return self._lm.generate(
            prompt, max_new_tokens=kwargs.get("max_tokens", self.max_new_tokens)
        )

    def paged_engine(self):
        """The paged KV decode engine behind :meth:`generate_batch` —
        question_answering.py probes this to size the llm scheduler's
        batches.  None only on the CPU backend, when it cannot be built;
        on a TPU backend that raises (kvcache/engine.py build_engine)."""
        return self._lm.paged_engine()

    def generate_batch(self, message_batches: list, **kwargs) -> list[str]:
        """Answer a whole coalesced batch in ONE decode-tier pass through
        the paged KV cache (mixed lengths, shared-prefix blocks mapped to
        the same physical blocks); on the CPU backend a serial loop
        stands in when the engine cannot be built."""
        prompts = []
        for messages in message_batches:
            if isinstance(messages, str):
                messages = prompt_chat_single_qa(messages)
            elif hasattr(messages, "value"):
                messages = messages.value
            prompts.append("\n".join(
                f"{m.get('role', 'user')}: {m.get('content', '')}"
                for m in messages
            ))
        return self._lm.generate_batch(
            prompts,
            max_new_tokens=kwargs.get("max_tokens", self.max_new_tokens),
        )


class OpenAIChat(BaseChat):
    def __init__(self, model: str = "gpt-4o-mini", *, api_key: str | None = None,
                 capacity=None, cache_strategy=None, retry_strategy=None, **kwargs):
        self.model = model
        self.api_key = api_key
        self.kwargs = kwargs

    def _call_llm(self, messages, **kwargs) -> str:
        try:
            import openai
        except ImportError as exc:
            raise ImportError("OpenAIChat requires the openai package") from exc
        client = openai.OpenAI(api_key=self.api_key)
        merged = {**self.kwargs, **kwargs}
        res = client.chat.completions.create(model=self.model, messages=messages, **merged)
        return res.choices[0].message.content


class LiteLLMChat(BaseChat):
    def __init__(self, model: str, *, cache_strategy=None, retry_strategy=None, **kwargs):
        self.model = model
        self.kwargs = kwargs

    def _call_llm(self, messages, **kwargs) -> str:
        try:
            import litellm
        except ImportError as exc:
            raise ImportError("LiteLLMChat requires litellm") from exc
        res = litellm.completion(model=self.model, messages=messages,
                                 **{**self.kwargs, **kwargs})
        return res["choices"][0]["message"]["content"]


class HFPipelineChat(BaseChat):
    """Local HuggingFace pipeline (transformers is baked in; weights must be
    available locally)."""

    def __init__(self, model: str, *, device: str = "cpu", call_kwargs=None, **kwargs):
        from transformers import pipeline

        self._pipe = pipeline("text-generation", model=model, device=device, **kwargs)
        self.call_kwargs = call_kwargs or {}

    def _call_llm(self, messages, **kwargs) -> str:
        prompt = "\n".join(m.get("content", "") for m in messages)
        out = self._pipe(prompt, **{**self.call_kwargs, **kwargs})
        return out[0]["generated_text"]


class BedrockChat(BaseChat):
    """AWS Bedrock chat via the Converse REST API, spoken natively with
    SigV4 (reference: xpacks/llm/llms.py:771 — boto3 wrapper; here the
    wire protocol is implemented directly like the kinesis/dynamodb
    connectors, with an injectable `_http` test seam).

    Credentials: explicit args or AWS_ACCESS_KEY_ID / AWS_SECRET_ACCESS_KEY
    / AWS_SESSION_TOKEN / AWS_REGION environment variables."""

    def __init__(self, model_id: str = "anthropic.claude-3-haiku-20240307-v1:0",
                 *, region: str | None = None, access_key: str | None = None,
                 secret_key: str | None = None, session_token: str | None = None,
                 endpoint: str | None = None, max_tokens: int = 512,
                 temperature: float | None = None, capacity=None,
                 cache_strategy=None, retry_strategy=None, _http=None,
                 **kwargs):
        import os

        self.model_id = model_id
        self.region = region or os.environ.get("AWS_REGION", "us-east-1")
        self.access_key = access_key or os.environ.get("AWS_ACCESS_KEY_ID", "")
        self.secret_key = secret_key or os.environ.get("AWS_SECRET_ACCESS_KEY", "")
        self.session_token = session_token or os.environ.get("AWS_SESSION_TOKEN")
        self.endpoint = endpoint
        self.max_tokens = max_tokens
        self.temperature = temperature
        self._http = _http
        self.kwargs = kwargs

    def _call_llm(self, messages, **kwargs) -> str:
        from ...io._aws import AwsCredentials, aws_rest_call

        creds = AwsCredentials(self.access_key, self.secret_key, self.region,
                               self.session_token)
        system = [
            {"text": m.get("content", "")}
            for m in messages if m.get("role") == "system"
        ]
        conv = [
            {"role": m.get("role", "user"),
             "content": [{"text": m.get("content", "")}]}
            for m in messages if m.get("role") != "system"
        ]
        inference: dict = {"maxTokens": kwargs.get("max_tokens",
                                                   self.max_tokens)}
        temp = kwargs.get("temperature", self.temperature)
        if temp is not None:
            inference["temperature"] = temp
        # extra Converse inference params (topP, stopSequences, ...) pass
        # through, constructor kwargs overridden by per-call kwargs
        for k, v in {**self.kwargs, **kwargs}.items():
            if k not in ("max_tokens", "temperature") and v is not None:
                inference[k] = v
        payload: dict = {"messages": conv, "inferenceConfig": inference}
        if system:
            payload["system"] = system
        out = aws_rest_call(
            creds, "bedrock-runtime", f"/model/{self.model_id}/converse",
            payload, endpoint=self.endpoint, _http=self._http,
        )
        return out["output"]["message"]["content"][0]["text"]


class CohereChat(BaseChat):
    def __init__(self, model: str = "command", **kwargs):
        self.model = model

    def _call_llm(self, messages, **kwargs):
        raise ImportError("CohereChat requires the cohere package")


__all__ = [
    "BaseChat", "JaxChat", "OpenAIChat", "LiteLLMChat", "HFPipelineChat",
    "CohereChat", "prompt_chat_single_qa",
]
