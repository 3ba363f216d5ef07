/* MPI-4 bigcount surface: MPI_Count overloads of
 * the count-taking core. A REAL >INT_MAX-element payload moves through
 * MPI_Send_c / MPI_Recv_c (2.2e9 MPI_CHAR = ~2.2 GB — this host has
 * the RAM), and the collective path is exercised with MPI_Allreduce_c.
 * Reference: ompi/mpi/bindings/ompi_bindings/c.py:296 (every
 * count-taking function emitted twice, the _c twin with MPI_Count).
 * Element count chosen via argv[1] so CI can also run a small smoke. */
#include <mpi.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>

static int rank, size;

#define CHECK(cond, code)                                            \
    do {                                                             \
        if (!(cond)) {                                               \
            fprintf(stderr, "rank %d: check failed at line %d\n",    \
                    rank, __LINE__);                                 \
            MPI_Abort(MPI_COMM_WORLD, code);                         \
        }                                                            \
    } while (0)

int main(int argc, char **argv)
{
    MPI_Init(&argc, &argv);
    MPI_Comm_rank(MPI_COMM_WORLD, &rank);
    MPI_Comm_size(MPI_COMM_WORLD, &size);
    CHECK(size >= 2, 1);
    CHECK(sizeof(MPI_Count) == 8, 2);

    MPI_Count n = (argc > 1) ? (MPI_Count)atoll(argv[1])
                             : ((MPI_Count)1 << 31) + 4096;

    /* ---- pt2pt: n MPI_CHAR, n > INT_MAX ------------------------- */
    if (rank < 2) {
        char *buf = malloc((size_t)n);
        CHECK(buf != NULL, 3);
        if (rank == 0) {
            memset(buf, 0x5a, (size_t)n);
            buf[0] = 1;
            buf[(size_t)n - 1] = 2;      /* probe both ends */
            CHECK(MPI_Send_c(buf, n, MPI_CHAR, 1, 30, MPI_COMM_WORLD)
                  == MPI_SUCCESS, 4);
        } else {
            memset(buf, 0, (size_t)n);
            MPI_Status st;
            CHECK(MPI_Recv_c(buf, n, MPI_CHAR, 0, 30, MPI_COMM_WORLD,
                             &st) == MPI_SUCCESS, 5);
            CHECK(buf[0] == 1 && buf[(size_t)n - 1] == 2, 6);
            CHECK(buf[(size_t)n / 2] == 0x5a, 7);
            /* the 64-bit count comes back intact */
            MPI_Count got = -1;
            CHECK(MPI_Get_count_c(&st, MPI_CHAR, &got) == MPI_SUCCESS,
                  8);
            CHECK(got == n, 9);
            /* the 32-bit query must refuse, not truncate */
            int small = 0;
            MPI_Get_count(&st, MPI_CHAR, &small);
            CHECK(small == MPI_UNDEFINED, 10);
        }
        free(buf);
    }
    MPI_Barrier(MPI_COMM_WORLD);

    /* ---- collectives: Allreduce_c / Bcast_c (modest count — the
     * 64-bit plumbing is what's under test here) ------------------ */
    {
        MPI_Count m = 1 << 16;
        float *v = malloc((size_t)m * sizeof(float));
        float *o = malloc((size_t)m * sizeof(float));
        for (MPI_Count i = 0; i < m; i++)
            v[i] = 1.0f;
        CHECK(MPI_Allreduce_c(v, o, m, MPI_FLOAT, MPI_SUM,
                              MPI_COMM_WORLD) == MPI_SUCCESS, 11);
        CHECK(o[0] == (float)size && o[m - 1] == (float)size, 12);

        if (rank == 0)
            for (MPI_Count i = 0; i < m; i++)
                v[i] = 3.0f;
        CHECK(MPI_Bcast_c(v, m, MPI_FLOAT, 0, MPI_COMM_WORLD)
              == MPI_SUCCESS, 13);
        CHECK(v[m - 1] == 3.0f, 14);

        MPI_Request r;
        CHECK(MPI_Isend_c(v, m, MPI_FLOAT, rank ^ 1, 31,
                          MPI_COMM_WORLD, &r) == MPI_SUCCESS, 15);
        float *w = malloc((size_t)m * sizeof(float));
        MPI_Status st;
        CHECK(MPI_Recv_c(w, m, MPI_FLOAT, rank ^ 1, 31,
                         MPI_COMM_WORLD, &st) == MPI_SUCCESS, 16);
        MPI_Wait(&r, MPI_STATUS_IGNORE);
        CHECK(w[m / 2] == 3.0f, 17);
        free(v);
        free(o);
        free(w);
    }

    /* ---- 64-bit type queries ------------------------------------ */
    {
        MPI_Count sz = -1, lb = -1, ext = -1;
        CHECK(MPI_Type_size_c(MPI_DOUBLE, &sz) == MPI_SUCCESS
              && sz == 8, 18);
        CHECK(MPI_Type_get_extent_c(MPI_DOUBLE, &lb, &ext)
              == MPI_SUCCESS && lb == 0 && ext == 8, 19);
        /* a contiguous type big enough that its total size only fits
         * in 64 bits */
        MPI_Datatype huge;
        CHECK(MPI_Type_contiguous_c(((MPI_Count)1 << 29) + 3, MPI_INT,
                                    &huge) == MPI_SUCCESS, 20);
        MPI_Type_commit(&huge);
        CHECK(MPI_Type_size_c(huge, &sz) == MPI_SUCCESS, 21);
        CHECK(sz == (((MPI_Count)1 << 29) + 3) * 4, 22);
        int sz32 = 0;
        MPI_Type_size(huge, &sz32);      /* must refuse, not truncate */
        CHECK(sz32 == MPI_UNDEFINED, 23);
        MPI_Type_free(&huge);
    }

    MPI_Barrier(MPI_COMM_WORLD);
    printf("OK c23_bigcount rank=%d/%d\n", rank, size);
    MPI_Finalize();
    return 0;
}
