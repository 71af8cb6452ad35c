"""serve.warmup: LLMServer._warmup() whole, its one block_until_ready included.
"""

from benchmarks.lib import start_spans

read = start_spans.reader("span_s", "serve.warmup")
