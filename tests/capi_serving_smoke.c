/* Standalone C host for the serving C ABI: start an engine (warmed
 * bucket lattice), submit concurrent-style requests, poll them back,
 * compare each against the single-request predictor, print stats.
 * Compiled + executed by tests/test_serving.py.
 * usage: capi_serving_smoke <model_dir> <n_requests> <feat> */
#include <math.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>

#include "paddle_tpu_capi.h"

int main(int argc, char** argv) {
  if (argc < 4) return 2;
  const char* model_dir = argv[1];
  int n_requests = atoi(argv[2]);
  int feat = atoi(argv[3]);

  PD_AnalysisConfig* cfg = PD_NewAnalysisConfig();
  PD_SetModel(cfg, model_dir, NULL);
  PD_DisableTPU(cfg);

  /* reference path: plain predictor, one request at a time */
  PD_Predictor* pred = PD_NewPredictor(cfg);
  if (!pred) {
    fprintf(stderr, "NewPredictor failed: %s\n", PD_GetLastError());
    return 1;
  }

  PD_ServingEngine* eng = PD_NewServingEngine(cfg, /*max_batch=*/4,
                                              /*max_seq=*/0,
                                              /*queue_depth=*/64,
                                              /*max_wait_ms=*/3,
                                              /*num_replicas=*/1);
  if (!eng) {
    fprintf(stderr, "NewServingEngine failed: %s\n", PD_GetLastError());
    return 1;
  }

  const char* in_name = PD_GetInputName(pred, 0);
  const char* out_name = PD_GetOutputName(pred, 0);

  float** bufs = (float**)malloc(sizeof(float*) * n_requests);
  int* rows = (int*)malloc(sizeof(int) * n_requests);
  int64_t* tickets = (int64_t*)malloc(sizeof(int64_t) * n_requests);
  for (int i = 0; i < n_requests; ++i) {
    rows[i] = 1 + i % 2;
    bufs[i] = (float*)malloc(sizeof(float) * rows[i] * feat);
    for (int j = 0; j < rows[i] * feat; ++j) {
      bufs[i][j] = (float)((i * 31 + j) % 13) * 0.125f - 0.75f;
    }
    int64_t shape[2] = {rows[i], feat};
    const int64_t* shapes[1] = {shape};
    const char* names[1] = {in_name};
    PD_DataType dtypes[1] = {PD_FLOAT32};
    int ndims[1] = {2};
    const void* datas[1] = {bufs[i]};
    tickets[i] = PD_ServingSubmit(eng, 1, names, dtypes, shapes, ndims,
                                  datas, /*priority=*/i % 3,
                                  /*deadline_ms=*/0);
    if (tickets[i] < 0) {
      fprintf(stderr, "Submit %d rejected: %s\n", i, PD_GetLastError());
      return 1;
    }
  }

  int matched = 0;
  for (int i = 0; i < n_requests; ++i) {
    PD_DataType dt;
    int64_t* oshape;
    int ndim;
    void* data;
    size_t nbytes;
    int rc;
    /* poll until served; engine workers batch behind the scenes */
    while ((rc = PD_ServingPoll(eng, tickets[i], out_name, &dt, &oshape,
                                &ndim, &data, &nbytes)) == 1) {
    }
    if (rc != 0) {
      fprintf(stderr, "Poll %d failed: %s\n", i, PD_GetLastError());
      return 1;
    }
    /* reference: same payload through the plain predictor */
    int64_t shape[2] = {rows[i], feat};
    PD_SetInput(pred, in_name, PD_FLOAT32, shape, 2, bufs[i]);
    if (PD_PredictorRun(pred)) {
      fprintf(stderr, "reference Run failed: %s\n", PD_GetLastError());
      return 1;
    }
    PD_DataType rdt;
    int64_t* rshape;
    int rndim;
    void* rdata;
    size_t rnbytes;
    PD_GetOutput(pred, out_name, &rdt, &rshape, &rndim, &rdata, &rnbytes);
    /* batched+padded vs single-request are two executables: equal within
       |a - b| <= 1e-6 + 1e-6 * |b| per element, not bit for bit */
    int same = nbytes == rnbytes && ndim == rndim && dt == rdt;
    for (size_t k = 0; same && k < nbytes / sizeof(float); ++k) {
      float a = ((const float*)data)[k], b = ((const float*)rdata)[k];
      if (!(fabsf(a - b) <= 1e-6f + 1e-6f * fabsf(b))) same = 0;
    }
    if (same) ++matched;
    PD_Free(oshape);
    PD_Free(data);
    PD_Free(rshape);
    PD_Free(rdata);
    PD_ServingRelease(eng, tickets[i]);
  }
  printf("matched=%d/%d\n", matched, n_requests);

  char* stats = PD_ServingStats(eng);
  if (!stats) {
    fprintf(stderr, "Stats failed: %s\n", PD_GetLastError());
    return 1;
  }
  printf("stats=%s\n", stats);
  PD_Free(stats);

  PD_DeleteServingEngine(eng); /* graceful drain */
  PD_DeletePredictor(pred);
  PD_DeleteAnalysisConfig(cfg);
  for (int i = 0; i < n_requests; ++i) free(bufs[i]);
  free(bufs);
  free(rows);
  free(tickets);
  if (matched != n_requests) return 1;
  printf("SERVING_CAPI_OK\n");
  return 0;
}
