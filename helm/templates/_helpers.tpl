{{/* Common naming + label helpers */}}
{{- define "tpustack.fullname" -}}
{{- .Release.Name | trunc 40 | trimSuffix "-" -}}
{{- end -}}

{{- define "tpustack.labels" -}}
app.kubernetes.io/part-of: tpu-production-stack
app.kubernetes.io/managed-by: {{ .Release.Service }}
helm.sh/chart: {{ printf "%s-%s" .Chart.Name .Chart.Version }}
{{- range $k, $v := .Values.servingEngineSpec.labels }}
{{ $k }}: {{ $v | quote }}
{{- end }}
{{- end -}}

{{/* Engine deployment name for one modelSpec entry */}}
{{- define "tpustack.engineName" -}}
{{- printf "%s-engine-%s" .release .spec.name | trunc 60 | trimSuffix "-" -}}
{{- end -}}

{{/* The engine serving command for one modelSpec entry — the TPU analogue of
     the reference's generated `vllm serve` args
     (deployment-vllm-multi.yaml:108-199) */}}
{{- define "tpustack.engineArgs" -}}
- "-m"
- "vllm_production_stack_tpu.engine.server"
- "--model"
- {{ .modelURL | quote }}
- "--served-model-name"
- {{ .name | quote }}
- "--port"
- "8000"
{{- if .maxModelLen }}
- "--max-model-len"
- {{ .maxModelLen | quote }}
{{- end }}
{{- if .dtype }}
- "--dtype"
- {{ .dtype | quote }}
{{- end }}
{{- if .quantization }}
- "--quantization"
- {{ .quantization | quote }}
{{- end }}
{{- if .tensorParallelSize }}
- "--tensor-parallel-size"
- {{ .tensorParallelSize | quote }}
{{- end }}
{{- if .maxNumSeqs }}
- "--max-num-seqs"
- {{ .maxNumSeqs | quote }}
{{- end }}
{{- if .numHostBlocks }}
- "--num-host-blocks"
- {{ .numHostBlocks | quote }}
{{- end }}
{{- if .hostKvGib }}
- "--host-kv-gib"
- {{ .hostKvGib | quote }}
{{- end }}
{{- if .diskKvGib }}
- "--disk-kv-dir"
- {{ .diskKvDir | default "/data/kv-cache" | quote }}
- "--disk-kv-gib"
- {{ .diskKvGib | quote }}
{{- end }}
{{- if .maxLoras }}
- "--max-loras"
- {{ .maxLoras | quote }}
{{- end }}
{{- if .sequenceParallelSize }}
- "--sequence-parallel-size"
- {{ .sequenceParallelSize | quote }}
{{- end }}
{{- if .expertParallelSize }}
- "--expert-parallel-size"
- {{ .expertParallelSize | quote }}
{{- end }}
{{- if .kvCacheDtype }}
- "--kv-cache-dtype"
- {{ .kvCacheDtype | quote }}
{{- end }}
{{- if .numSpeculativeTokens }}
- "--num-speculative-tokens"
- {{ .numSpeculativeTokens | quote }}
{{- end }}
{{- if .speculativeConfig }}
- "--speculative-config"
- {{ .speculativeConfig | quote }}
{{- end }}
{{- if .draftModel }}
- "--draft-model"
- {{ .draftModel | quote }}
{{- end }}
{{- if .decodeWindow }}
- "--decode-window"
- {{ .decodeWindow | quote }}
{{- end }}
{{- if .maxWaitingRequests }}
- "--max-waiting-requests"
- {{ .maxWaitingRequests | quote }}
{{- end }}
{{- if .maxQueuedTokens }}
- "--max-queued-tokens"
- {{ .maxQueuedTokens | quote }}
{{- end }}
{{- if .drainTimeoutS }}
- "--drain-timeout-s"
- {{ .drainTimeoutS | quote }}
{{- end }}
{{- if eq (.requestTracing | default true) false }}
- "--request-tracing"
- "false"
{{- end }}
{{- if .traceBuffer }}
- "--trace-buffer"
- {{ .traceBuffer | quote }}
{{- end }}
{{- if .kvHydration }}
- "--kv-hydration"
- {{ .kvHydration | quote }}
{{- end }}
{{- if .kvHydrationChunkBlocks }}
- "--kv-hydration-chunk-blocks"
- {{ .kvHydrationChunkBlocks | quote }}
{{- end }}
{{- if .kvHydrationTimeoutS }}
- "--kv-hydration-timeout-s"
- {{ .kvHydrationTimeoutS | quote }}
{{- end }}
{{- if .kvAtRestCodec }}
- "--kv-at-rest-codec"
- {{ .kvAtRestCodec | quote }}
{{- end }}
{{- if .kvAtRestGroupSize }}
- "--kv-at-rest-group-size"
- {{ .kvAtRestGroupSize | quote }}
{{- end }}
{{- if .kvAtRestHostRing }}
- "--kv-at-rest-host-ring"
- "true"
{{- end }}
{{- if .kvPeerFetch }}
- "--kv-peer-fetch"
- "true"
{{- end }}
{{- if .kvPeerFetchTimeoutS }}
- "--kv-peer-fetch-timeout-s"
- {{ .kvPeerFetchTimeoutS | quote }}
{{- end }}
{{- if .kvPeerTransport }}
- "--kv-peer-transport"
- {{ .kvPeerTransport | quote }}
{{- end }}
{{- if .structuredOutput }}
- "--structured-output"
- {{ .structuredOutput | quote }}
{{- end }}
{{- if .postmortemDir }}
- "--postmortem-dir"
- {{ .postmortemDir | quote }}
{{- end }}
{{- if .watchdogStallS }}
- "--watchdog-stall-s"
- {{ .watchdogStallS | quote }}
{{- end }}
{{- if eq (.enablePrefixCaching | default true) false }}
- "--no-enable-prefix-caching"
{{- end }}
{{- if .compileStormThreshold }}
- "--compile-storm-threshold"
- {{ .compileStormThreshold | quote }}
{{- end }}
{{- if .compileStormWindowS }}
- "--compile-storm-window-s"
- {{ .compileStormWindowS | quote }}
{{- end }}
{{- range .extraArgs }}
- {{ . | quote }}
{{- end }}
{{- end -}}
